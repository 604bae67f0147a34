"""serve.prefill_share: device time of the engine's admission programs
(prefill and writing the cache pages) over that of admission and decode
programs together, in the traced window."""
from bench.common import BenchError
from bench.readers import seconds_matching

ADMIT, STEP = r"^jit_admit", r"^jit_step"


def read(run):
    tr = run.get("trace")
    if not tr or not run["record"]["prompts_in_window"]:
        return None
    a, na = seconds_matching(tr["modules"], ADMIT)
    s, _ns = seconds_matching(tr["modules"], STEP)
    if na == 0:
        raise BenchError(f"prompts were admitted in the window, but the trace "
                         f"has no program matching {ADMIT!r}")
    return 100.0 * a / (a + s)
