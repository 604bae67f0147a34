"""Where the Pallas kernels run: the one place interpret mode is decided."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for this process.

    ``None`` (every production caller) derives it from the platform: the
    interpreter on the CPU, where the TPU kernels cannot compile, and the
    compiled kernel on an accelerator, so a kernel on the chip never runs
    the interpreter. An explicit bool is the test hook that forces it."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
