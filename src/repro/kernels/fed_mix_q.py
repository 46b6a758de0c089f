"""Pallas TPU kernel for the fused *quantized* dense mixing operator.

    O = M_new @ dequant(Q_new, scales) + M_old @ X_old

the int8-wire form of ``kernels.fed_mix``: X_new arrives as the
``Int8Codec`` record — int8 values [D, Pq] plus one float32 absmax scale
per ``chunk`` consecutive params [D, Pq/chunk] — and is dequantized
*inline in the MXU contraction loop*. Each grid step loads an int8
[bk, bd] tile (4X less HBM->VMEM traffic than f32) and its [bk, bd/chunk]
scale tile, broadcasts each scale column across its ``chunk`` lanes,
multiplies, and feeds the MXU — so the dense path never materializes a
full-precision copy of the quantized client buffer in HBM: the f32 tile
lives only in a VMEM scratch for the duration of one contraction step.

The scales are handed to the kernel as [Pq/block_d, D, block_d/chunk]:
a Mosaic block's last dim must be a multiple of 128 lanes or the whole
array dim, and a [bk, bd/chunk] window of the flat [D, Pq/chunk] layout
is neither. Splitting the scale columns per param tile makes each
block's last dim the whole (leading-squeezed) array dim.

Grid/accumulator structure is identical to ``fed_mix`` (one grid step per
(D-row-block, param-tile, K-block), two MXU contractions into a single f32
VMEM scratch accumulator persisted across K steps, output stored once on
the last K step). ``chunk`` must divide ``block_d`` so scale boundaries
never straddle a param tile.
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


def _fed_mix_q_kernel(mn_ref, mo_ref, qn_ref, sc_ref, xo_ref, o_ref,
                      acc_scr, xn_scr, *, nk: int, chunk: int):
    # mn/mo: [br, bk] f32; qn: [bk, bd] int8; sc: [bk, bd/chunk] f32;
    # xo: [bk, bd]; o: [br, bd]; acc: [br, bd] f32; xn: [bk, bd] f32
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # inline dequant, one chunk of lanes at a time: its scale column
    # broadcasts across the chunk — the f32 tile exists only in VMEM
    for c in range(xn_scr.shape[1] // chunk):
        cols = pl.ds(c * chunk, chunk)
        xn_scr[:, cols] = (qn_ref[:, cols].astype(jnp.float32)
                           * sc_ref[:, c:c + 1])

    dims = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(
        mn_ref[...], xn_scr[...],
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
                   static_argnames=("chunk", "out_dtype", "block_r",
                                    "block_d", "block_k", "interpret"))
def fed_mix_q(m_new: jnp.ndarray, m_old: jnp.ndarray,
              q_new: jnp.ndarray, scales: jnp.ndarray,
              x_old: jnp.ndarray, *, chunk: int = 256,
              out_dtype=None,
              block_r: int = DEFAULT_BLOCK_R,
              block_d: int = DEFAULT_BLOCK_D,
              block_k: int = DEFAULT_BLOCK_K,
              interpret: bool | None = None) -> jnp.ndarray:
    """m_new, m_old: [D, D]; q_new: int8 [D, Pq] (Pq a multiple of
    ``chunk`` — the ``Int8Codec.encode`` layout); scales: f32
    [D, Pq/chunk]; x_old: [D, P] with P <= Pq -> [D, P].

    f32 accumulation; output dtype defaults to ``x_old.dtype``. D is padded
    to the row/K blocks and Pq to ``block_d`` internally (zero int8 values
    contribute exactly 0.0). ``interpret=None`` auto-detects the backend.
    """
    interpret = default_interpret(interpret)
    out_dtype = x_old.dtype if out_dtype is None else out_dtype
    d, pq = q_new.shape
    p = x_old.shape[1]
    if pq % chunk:
        raise ValueError(f"q_new columns ({pq}) not a multiple of "
                         f"chunk ({chunk})")
    if pq < p:
        raise ValueError(f"q_new covers {pq} params < x_old's {p}")
    # param tile must hold whole chunks so scale boundaries never straddle
    # it: round block_d up to the next chunk multiple (non-divisor chunks,
    # e.g. 192, just get a slightly larger tile instead of an error)
    bd = max(block_d, chunk)
    bd = bd + (-bd) % chunk
    br = min(block_r, -(-d // 16) * 16)
    bk = min(block_k, -(-d // 16) * 16)
    dpr = d + (-d) % br                   # output-row padding
    dpk = d + (-d) % bk                   # contraction padding
    pad_p = (-pq) % bd
    pp = pq + pad_p
    mn = jnp.pad(m_new.astype(jnp.float32), ((0, dpr - d), (0, dpk - d)))
    mo = jnp.pad(m_old.astype(jnp.float32), ((0, dpr - d), (0, dpk - d)))
    qn = jnp.pad(q_new, ((0, dpk - d), (0, pad_p)))
    nc = bd // chunk                      # scale columns per param tile
    sc = jnp.pad(scales, ((0, dpk - d), (0, pad_p // chunk)))
    sc = sc.reshape(dpk, pp // bd, nc).transpose(1, 0, 2)
    xo = jnp.pad(x_old, ((0, dpk - d), (0, pp - p)))
    nk = dpk // bk
    out = pl.pallas_call(
        functools.partial(_fed_mix_q_kernel, nk=nk, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((dpr, pp), out_dtype),
        grid=(dpr // br, pp // bd, nk),
        in_specs=[
            pl.BlockSpec((br, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((br, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bd), lambda i, j, k: (k, j)),
            pl.BlockSpec((None, bk, nc), lambda i, j, k: (j, k, 0)),
            pl.BlockSpec((bk, bd), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((br, bd), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((br, bd), jnp.float32),
                        pltpu.VMEM((bk, bd), jnp.float32)],
        interpret=interpret,
    )(mn, mo, qn, sc, xo)
    return out[:d, :p]
