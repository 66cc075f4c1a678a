"""Pallas TPU kernels (each: <name>.py kernel + ops.py dispatch + ref.py oracle).

Every kernel entry point takes ``interpret: bool | None = None``.  ``None``
leaves the choice to :func:`resolve_interpret`, the one place that decides
whether a kernel runs compiled or through the Pallas interpreter; callers
above the kernels pass ``interpret`` through untouched.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    An explicit bool wins.  ``None`` follows the default backend: the CPU
    interprets (the validation path tests run on), the TPU compiles, and any
    other backend raises, so a kernel never runs interpreted on an
    accelerator without the caller having asked for it.
    """
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas route for backend {backend!r}: the kernels compile for "
        "the TPU and are interpreted on the CPU; pass interpret=True to "
        "interpret them here"
    )
