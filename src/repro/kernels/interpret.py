"""Call-time choice between compiled Pallas kernels and interpret mode.

Mosaic compiles the kernels for a TPU; on the CPU they run in Pallas
interpret mode (the kernel body executes as ordinary XLA ops, bit-level
semantics intact).  The choice is made when a kernel is CALLED, from the
platform its arrays live on — never when a module is imported, so
importing ``repro.kernels`` does not start a JAX backend.
"""

from __future__ import annotations

import jax

__all__ = ["resolve_interpret", "platform_interpret"]


def platform_interpret(platform: str) -> bool:
    """Interpret mode exactly where the arrays live on the CPU."""
    return platform == "cpu"


def resolve_interpret(interpret: bool | None, *arrays) -> bool:
    """An explicit ``interpret`` wins; otherwise interpret mode iff the
    first concrete array among ``arrays`` lives on the CPU.  Tracers and
    host (numpy) inputs carry no device, so they fall back to the
    platform JAX places new arrays on (``jax.default_backend()``)."""
    if interpret is not None:
        return bool(interpret)
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            return platform_interpret(next(iter(a.devices())).platform)
    return platform_interpret(jax.default_backend())
