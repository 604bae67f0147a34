"""serve.queue_ms: median wait in the router's queue, from a request's due
time to its admission into the engine (host clock)."""
from bench.common import percentile


def read(run):
    xs = run["record"]["queue_s"]
    return 1e3 * percentile(xs, 50) if xs else None
