"""serve.step_ms: median duration of the program span ``serve.step``, one
decode step of the engine from its call until the tokens are on the host
and the evictions dispatched, over the traced window."""
from bench import spans


def read(run):
    s = spans.find(run, "serve.step", bool(run["record"]["rows_per_step"]))
    return None if s is None else 1e3 * s["p50_s"]
