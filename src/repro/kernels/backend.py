"""Backend detection shared by the Pallas kernel modules and their wrappers.

Kept dependency-free (no intra-package imports) so both the low-level kernel
modules (``fed_aggregate``, ``fed_mix``, ...) and the dispatching wrappers in
``ops`` can use it without cycles.
"""
from __future__ import annotations

import jax

#: precision of every mixing contraction (the kernels and their jnp
#: oracles): full f32. At the TPU's default, XLA and Mosaic both round f32
#: operands to bf16: averaging 256 unit-scale client rows on a v5e then
#: errs by 7.7e-4 instead of 5e-8, which erases any client update smaller
#: than a bf16 step of its parameter. CPU backends compute f32 either way.
F32_CONTRACT = jax.lax.Precision.HIGHEST


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret(interpret: bool | None) -> bool:
    """Resolve an ``interpret=None`` kernel default against the backend:
    Mosaic-native on TPU, the Pallas interpreter everywhere else. A kernel
    called directly (not through ``ops``) must never silently run interpreted
    on real hardware."""
    return (not on_tpu()) if interpret is None else bool(interpret)
