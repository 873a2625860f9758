"""Pallas kernels: the TPU analogues of the modelled dataflows.

Every kernel entry point takes ``interpret: bool | None = None``.  ``None``
means "decide from the backend", and :func:`resolve_interpret` is the one
place that decision is made: compiled for the chip on a TPU backend,
Pallas interpret mode everywhere else (the CPU test run).  An explicit
``interpret=False`` compiles for the chip even where the default backend is
the CPU — that is how the chip-compile tests lower for a described TPU.
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode: as asked, else iff the default backend is no TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
