"""Shared arithmetic of the metric readers: a kernel's or a program's time
in the reduced trace, and a share of the roofline."""
from __future__ import annotations

import re
from typing import Optional, Tuple

from bench.common import BenchError


def seconds_matching(table: dict, pattern: str) -> Tuple[float, int]:
    """Summed seconds and count of the trace entries whose name matches."""
    rx = re.compile(pattern)
    s = n = 0
    for name, v in table.items():
        if rx.search(name):
            s += v["s"]
            n += v["n"]
    return s, n


def kernel_seconds(trace: dict, program: str, kernel: str) -> Tuple[float, int]:
    """A kernel's device time: the custom calls run inside the programs
    whose name matches ``program``; failing those, the operations whose
    own name matches ``kernel``. Called only where the run did the kernel's
    work, so a trace in which neither is found is an error: the names the
    compiler gives have changed, and the roofline would fall silent."""
    secs, n = seconds_matching(trace.get("kernels", {}), program)
    if n == 0:
        secs, n = seconds_matching(trace["ops"], kernel)
    if n == 0:
        raise BenchError(f"the run did the work of kernel {kernel!r}, but the "
                         f"trace has no custom call in a program matching "
                         f"{program!r} and no operation matching {kernel!r}")
    return secs, n


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> Optional[float]:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bandwidth) over the time taken, in %."""
    if seconds <= 0:
        return None
    bound = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
