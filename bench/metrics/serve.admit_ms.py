"""serve.admit_ms: median duration of the program span ``serve.admit`` over
the admissions that took a slot (``admitted`` 1: page reservation, prefill
and the cache write; refusals are left out), over the traced window."""
from bench import common, spans


def read(run):
    s = spans.find(run, "serve.admit", bool(run["record"]["prompts_in_window"]))
    if s is None:
        return None
    took = s["by"].get("admitted", {}).get("1")
    if took is None:
        raise common.BenchError("the run admitted requests, but no serve.admit "
                                "span carries admitted=1")
    return 1e3 * took["p50_s"]
