"""fed.eval_ms: mean duration of the program span ``fed.eval``, one
round's evaluation of the global model on the host, over the traced
window."""
from bench import spans


def read(run):
    rounds = sum(len(j["rounds"]) for j in run["record"]["jobs"])
    s = spans.find(run, "fed.eval", rounds > 0)
    return None if s is None else 1e3 * s["s"] / s["n"]
