"""serve.batch_occupancy: mean rows in a decode step over the engine's
slots, across the window's steps (the engine's active-row count)."""


def read(run):
    rows = run["record"]["rows_per_step"]
    if not rows:
        return None
    return 100.0 * sum(rows) / len(rows) / run["record"]["num_slots"]
