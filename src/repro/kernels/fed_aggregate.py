"""Pallas TPU kernel for the paper's ``Aggregate(·)`` operator.

out[d] = sum_n w[n] * x[n, d] over N client/cluster replicas of a flattened
parameter vector — the compute hot-spot of every FedP2P/FedAvg round at
production model sizes (N x |theta| reads).

TPU mapping: the reduction is a [1, N] x [N, Bd] matvec per parameter tile,
so each grid step is one MXU pass over a VMEM-resident tile; the parameter
dimension is tiled in ``block_d`` lanes (multiple of 128). Weights are
broadcast to every grid step (block index 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import F32_CONTRACT, default_interpret

DEFAULT_BLOCK_D = 2048


def _fed_aggregate_kernel(w_ref, x_ref, o_ref):
    # w_ref: [1, N] f32; x_ref: [N, bd]; o_ref: [1, bd]
    x = x_ref[...].astype(jnp.float32)
    acc = jax.lax.dot_general(
        w_ref[...], x,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=F32_CONTRACT)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fed_aggregate(x: jnp.ndarray, w: jnp.ndarray, *,
                  block_d: int = DEFAULT_BLOCK_D,
                  interpret: bool | None = None) -> jnp.ndarray:
    """x: [N, D] stacked flat params; w: [N] aggregation weights -> [D].

    D is padded to a multiple of ``block_d`` internally. ``interpret=None``
    auto-detects the backend (native Mosaic on TPU, interpreter elsewhere) —
    a direct call on TPU must never silently run interpreted.
    """
    interpret = default_interpret(interpret)
    n, d = x.shape
    pad = (-d) % block_d
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    dp = d + pad
    out = pl.pallas_call(
        _fed_aggregate_kernel,
        out_shape=jax.ShapeDtypeStruct((1, dp), x.dtype),
        grid=(dp // block_d,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        interpret=interpret,
    )(w.reshape(1, n).astype(jnp.float32), x)
    return out[0, :d]
