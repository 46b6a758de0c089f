"""Pallas TPU kernel for the fused dense mixing operator.

    O = M_new @ X_new + M_old @ X_old

with M_new/M_old the [D, D] client-mixing matrices every ``Protocol``
emits (``f_out = M_new @ f_new + M_old @ f_old``) and X_new/X_old the
[D, P] flat-packed client parameter buffers (``kernels.ops.pack_tree``).
This is the hot spot of ``DenseEngine.run_rounds`` at paper scale: the
unfused form is 2·|leaves| separate [D, D] @ [D, leaf] matmuls that
re-read both mixing matrices and re-flatten every leaf per call.

TPU mapping: grid (D-row-blocks, param-tiles, K-blocks) with the
contraction (client) axis minor/sequential — each step does TWO MXU
contractions ([br, bk] @ [bk, bd], new then old) into one f32 VMEM
scratch accumulator persisted across K steps (the flash-kernel state
pattern), and the output tile is stored exactly once on the last K step.
The parameter dimension is tiled in ``block_d`` lanes (multiple of 128)
like ``fed_aggregate``; K tiling in ``block_k`` keeps the X tiles
VMEM-resident at production client counts (D ~ thousands) instead of
loading the full [D, block_d] slab per step. D is zero-padded to the
row/K tiles — zero K-columns contribute exactly 0.0 to the f32
accumulator, and padded output rows are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import F32_CONTRACT, default_interpret

DEFAULT_BLOCK_R = 128
DEFAULT_BLOCK_D = 2048
DEFAULT_BLOCK_K = 256


def _fed_mix_kernel(mn_ref, mo_ref, xn_ref, xo_ref, o_ref, acc_scr, *,
                    nk: int):
    # mn/mo: [br, bk] f32; xn/xo: [bk, bd]; o: [br, bd]; acc: [br, bd] f32
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    dims = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(
        mn_ref[...], xn_ref[...].astype(jnp.float32),
        dimension_numbers=dims, preferred_element_type=jnp.float32,
        precision=F32_CONTRACT)
    acc = acc + jax.lax.dot_general(
        mo_ref[...], xo_ref[...].astype(jnp.float32),
        dimension_numbers=dims, preferred_element_type=jnp.float32,
        precision=F32_CONTRACT)
    acc_scr[...] += acc

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_d", "block_k",
                                    "interpret"))
def fed_mix(m_new: jnp.ndarray, m_old: jnp.ndarray,
            x_new: jnp.ndarray, x_old: jnp.ndarray, *,
            block_r: int = DEFAULT_BLOCK_R,
            block_d: int = DEFAULT_BLOCK_D,
            block_k: int = DEFAULT_BLOCK_K,
            interpret: bool | None = None) -> jnp.ndarray:
    """m_new, m_old: [D, D]; x_new, x_old: [D, P] -> [D, P] in x_new.dtype.

    f32 accumulation regardless of input dtype. D is padded to the row and
    K blocks (each clamped to D's sublane round-up, so tiny simulator-scale
    client counts don't pay full-size grid steps) and P to ``block_d``
    internally. ``interpret=None`` auto-detects the backend — native Mosaic
    on TPU, interpreter elsewhere.
    """
    interpret = default_interpret(interpret)
    d, p = x_new.shape
    br = min(block_r, -(-d // 16) * 16)
    bk = min(block_k, -(-d // 16) * 16)
    dpr = d + (-d) % br                   # output-row padding
    dpk = d + (-d) % bk                   # contraction padding
    pad_p = (-p) % block_d
    pp = p + pad_p
    mn = jnp.pad(m_new.astype(jnp.float32), ((0, dpr - d), (0, dpk - d)))
    mo = jnp.pad(m_old.astype(jnp.float32), ((0, dpr - d), (0, dpk - d)))
    xn = jnp.pad(x_new, ((0, dpk - d), (0, pad_p)))
    xo = jnp.pad(x_old, ((0, dpk - d), (0, pad_p)))
    nk = dpk // bk
    out = pl.pallas_call(
        functools.partial(_fed_mix_kernel, nk=nk),
        out_shape=jax.ShapeDtypeStruct((dpr, pp), x_new.dtype),
        grid=(dpr // br, pp // block_d, nk),
        in_specs=[
            pl.BlockSpec((br, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((br, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, block_d), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, block_d), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((br, block_d), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((br, block_d), jnp.float32)],
        interpret=interpret,
    )(mn, mo, xn, xo)
    return out[:d, :p]
