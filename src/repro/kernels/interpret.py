"""Where the Pallas kernels run: compiled by Mosaic on a TPU, in the Pallas
interpreter on every other backend (so CPU tests exercise the kernel bodies).

Every kernel wrapper takes ``interpret=None`` and resolves it here, so the
decision lives in one place and a TPU process never runs the interpreter.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(requested: Optional[bool] = None) -> bool:
    """False on a TPU backend, whatever was requested; elsewhere `requested`,
    defaulting to True (Mosaic cannot compile for a CPU backend).  A test that
    compiles a kernel for a described TPU passes ``False`` explicitly."""
    if jax.default_backend() == "tpu":
        return False
    return True if requested is None else bool(requested)
