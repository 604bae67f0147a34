"""fed.merge_host_ms: mean duration of the program span ``fed.merge_host``:
the host's part of a merge round (the plan from the device's assignment,
the shard bookkeeping and its re-upload), over the traced window."""
from bench import spans


def read(run):
    merges = sum(r["merge"] for j in run["record"]["jobs"] for r in j["rounds"])
    s = spans.find(run, "fed.merge_host", merges > 0)
    return None if s is None else 1e3 * s["s"] / s["n"]
