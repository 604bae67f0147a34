"""fed.upload_mb_per_round: bytes the program put on the device as client
shards (the ``nbytes`` counter of ``fed.upload_shards``) over the rounds
completed in the traced window, in MB (1e6 bytes)."""
from bench import spans


def read(run):
    rounds = sum(len(j["rounds"]) for j in run["record"]["jobs"])
    s = spans.find(run, "fed.upload_shards", rounds > 0)
    return None if s is None else s["args"].get("nbytes", 0.0) / rounds / 1e6
