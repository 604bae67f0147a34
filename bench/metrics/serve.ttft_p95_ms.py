"""serve.ttft_p95_ms: the 95th percentile over every request sent in the
window of its first token's arrival minus its due time (host clock), read
in the traced run. The chat cell sends about 14 requests a window, too few
for this tail to hold an end-to-end bound."""
from bench.common import percentile


def read(run):
    xs = run["record"]["ttft_s"]
    return 1e3 * percentile(xs, 95) if xs else None
