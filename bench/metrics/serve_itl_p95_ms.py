"""serve_itl_p95_ms: 95th percentile over every gap between consecutive
output tokens of every request sent in the window (host clock)."""
from bench.common import percentile


def read(run):
    xs = run["record"]["itl_s"]
    return 1e3 * percentile(xs, 95) if xs else None
