"""Pallas tile kernels for the IWPP hot spot (DESIGN.md §2) and the one
place that decides whether they run compiled or in interpret mode."""

from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """True exactly when the default JAX backend is not a TPU.

    Mosaic compiles the kernels for a TPU only; on every other backend
    they run through the Pallas interpreter.
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; ``None`` means :func:`default_interpret`."""
    return default_interpret() if interpret is None else bool(interpret)
