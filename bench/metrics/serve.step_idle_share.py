"""serve.step_idle_share: share of the time inside ``serve.step`` spans in
which no operation ran on the chip (the step's host work: dispatch, the
token read-back, bookkeeping, evictions), in %."""
from bench import spans


def read(run):
    s = spans.find(run, "serve.step", bool(run["record"]["rows_per_step"]))
    if s is None or s["s"] <= 0:
        return None
    return 100.0 * s["idle_s"] / s["s"]
