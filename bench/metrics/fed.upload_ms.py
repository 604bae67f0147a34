"""fed.upload_ms: mean duration of the program span ``fed.upload_shards``,
one upload of the flat client shards to the device (at a job's set-up and
after its merge), over the traced window."""
from bench import spans


def read(run):
    s = spans.find(run, "fed.upload_shards", bool(run["record"]["jobs"]))
    return None if s is None else 1e3 * s["s"] / s["n"]
