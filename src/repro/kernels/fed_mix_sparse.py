"""Pallas TPU kernels for the structured-sparse mixing fast path.

The dense mixing operator (``kernels/fed_mix.py``) computes every round as
``[D, D] @ [D, P]`` — O(D²·P) FLOPs and an O(D²) matrix materialization even
when the round's collaboration structure touches two rows per client. Every
registered protocol's structure is one of two ``MixingSpec`` forms
(``protocols.spec``), and each gets its own kernel here:

* ``fed_mix_segment`` — cluster-segment form (FedAvg, FedP2P; the global
  rank-1 server term is the L=1 case):

      out_i = sum_{j: c(j)=c(i)} (w_new_j x_new_j + w_old_j x_old_j)

  lowered as ONE pass over X: a per-cluster segment reduce (the weights are
  folded into two skinny one-hot matrices, so the reduce is an
  ``[L, bk] @ [bk, bd]`` MXU contraction accumulated over D-blocks — the
  fed_mix K-loop pattern with L rows instead of D) followed by a
  gather-broadcast back to member rows (``[br, L] @ [L, bd]``). Total
  O(D·L·P) MXU FLOPs — for L ≪ D this is the O(D·P) fast path (at
  D=4096, L=8: ~340X fewer FLOPs than the dense kernel, and no [D, D]
  operand ever exists).

* ``fed_mix_matching`` — permutation form (gossip's two ring phases, one
  random perfect matching for ``gossip_async``): straggler-substitute
  ``eff = s·x_new + (1-s)·x_old`` once, then per stage average every row
  with its partner row. The [D]-indexed row gather stays an XLA gather
  (a matching is not block-alignable, and the op is purely bandwidth-bound
  — O(D·P) bytes, zero FLOPs); the halving-add runs as a tiled VPU kernel.

Backend dispatch mirrors every other kernel: ``interpret=None`` auto-detects
(native Mosaic on TPU, interpreter elsewhere); CPU production paths call the
jnp oracles in ``kernels/ref.py`` via ``kernels.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import F32_CONTRACT, default_interpret

DEFAULT_BLOCK_R = 256
DEFAULT_BLOCK_D = 512
DEFAULT_BLOCK_K = 512


def _segment_reduce_kernel(cn_ref, co_ref, xn_ref, xo_ref, seg_ref, acc_scr,
                           *, nk: int):
    # cn/co: [Lp, bk] f32 (weights folded in); xn/xo: [bk, bd];
    # seg/acc: [Lp, bd] f32 — accumulated across the K (client-block) axis.
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    dims = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(
        cn_ref[...], xn_ref[...].astype(jnp.float32),
        dimension_numbers=dims, preferred_element_type=jnp.float32,
        precision=F32_CONTRACT)
    acc = acc + jax.lax.dot_general(
        co_ref[...], xo_ref[...].astype(jnp.float32),
        dimension_numbers=dims, preferred_element_type=jnp.float32,
        precision=F32_CONTRACT)
    acc_scr[...] += acc

    @pl.when(ik == nk - 1)
    def _emit():
        seg_ref[...] = acc_scr[...]


def _gather_broadcast_kernel(c_ref, seg_ref, o_ref):
    # c: [br, Lp] one-hot membership; seg: [Lp, bd]; o = c @ seg.
    o_ref[...] = jax.lax.dot_general(
        c_ref[...], seg_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=F32_CONTRACT).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_r", "block_d",
                                    "block_k", "interpret"))
def fed_mix_segment(cluster_ids: jnp.ndarray, w_new: jnp.ndarray,
                    w_old: jnp.ndarray, x_new: jnp.ndarray,
                    x_old: jnp.ndarray, *, num_segments: int,
                    block_r: int = DEFAULT_BLOCK_R,
                    block_d: int = DEFAULT_BLOCK_D,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None) -> jnp.ndarray:
    """cluster_ids [D] i32; w_new/w_old [D]; x_new/x_old [D, P] -> [D, P].

    Structured-sparse mixing for cluster-segment specs, in x_new.dtype with
    f32 accumulation. No operand is padded to the tiles when it need not
    be: the [L, P] segment sums keep exactly L rows, D stays unpadded while
    it fits one row/K block (a block spanning a whole dim is always legal),
    and the last ``block_d`` param tile is ragged — every column is mixed
    independently, so its out-of-range lanes only reach output lanes that
    are never stored. At LM widths a padded copy of a [D, P] operand is
    gigabytes. D beyond one block is zero-padded (zero rows contribute
    exactly 0 to the sums). The dense [D, D] operator is never formed.
    """
    interpret = default_interpret(interpret)
    d, p = x_new.shape
    lp = max(1, num_segments)
    br = d if d <= block_r else block_r
    bk = d if d <= block_k else block_k
    dpr = d + (-d) % br                   # gather-phase row padding
    dpk = d + (-d) % bk                   # reduce-phase contraction padding
    n_p = pl.cdiv(p, block_d)

    onehot = jax.nn.one_hot(cluster_ids, lp, dtype=jnp.float32)     # [D, L]
    cn = jnp.pad((onehot * w_new.astype(jnp.float32)[:, None]).T,
                 ((0, 0), (0, dpk - d)))                            # [L, Dk]
    co = jnp.pad((onehot * w_old.astype(jnp.float32)[:, None]).T,
                 ((0, 0), (0, dpk - d)))
    xn = jnp.pad(x_new, ((0, dpk - d), (0, 0))) if dpk > d else x_new
    xo = jnp.pad(x_old, ((0, dpk - d), (0, 0))) if dpk > d else x_old
    nk = dpk // bk

    seg = pl.pallas_call(
        functools.partial(_segment_reduce_kernel, nk=nk),
        out_shape=jax.ShapeDtypeStruct((lp, p), jnp.float32),
        grid=(n_p, nk),
        in_specs=[
            pl.BlockSpec((lp, bk), lambda j, k: (0, k)),
            pl.BlockSpec((lp, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk, block_d), lambda j, k: (k, j)),
            pl.BlockSpec((bk, block_d), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((lp, block_d), lambda j, k: (0, j)),
        scratch_shapes=[pltpu.VMEM((lp, block_d), jnp.float32)],
        interpret=interpret,
    )(cn, co, xn, xo)

    c_rows = jnp.pad(onehot, ((0, dpr - d), (0, 0)))                # [Dr, L]
    out = pl.pallas_call(
        _gather_broadcast_kernel,
        out_shape=jax.ShapeDtypeStruct((dpr, p), x_new.dtype),
        grid=(dpr // br, n_p),
        in_specs=[
            pl.BlockSpec((br, lp), lambda i, j: (i, 0)),
            pl.BlockSpec((lp, block_d), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((br, block_d), lambda i, j: (i, j)),
        interpret=interpret,
    )(c_rows, seg)
    return out[:d] if dpr > d else out


def _pair_average_kernel(a_ref, b_ref, o_ref):
    # o = 0.5 * (a + b): one matching stage on pre-gathered partner rows.
    o_ref[...] = 0.5 * (a_ref[...] + b_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_d", "interpret"))
def fed_mix_matching(perms: jnp.ndarray, survive: jnp.ndarray,
                     x_new: jnp.ndarray, x_old: jnp.ndarray, *,
                     block_r: int = DEFAULT_BLOCK_R,
                     block_d: int = DEFAULT_BLOCK_D,
                     interpret: bool | None = None) -> jnp.ndarray:
    """perms [S, D] i32 (stage partner maps, perm[i]=i for byes);
    survive [D] 0/1; x_new/x_old [D, P] -> [D, P] in x_new.dtype.

    Permutation-gather mixing: straggler-substitute once, then per stage
    average every row with its partner row (byes average with themselves —
    exact in float). The per-stage row gather is an XLA take (bandwidth-
    bound, no block structure to exploit); the VPU halving-add is the
    Pallas-tiled part. Everything is O(S·D·P) — no [D, D] operator. As in
    ``fed_mix_segment``, D is padded only past one row block and the last
    param tile is ragged (the halving-add is elementwise).
    """
    interpret = default_interpret(interpret)
    d, p = x_new.shape
    br = d if d <= block_r else block_r
    pad_r = (-d) % br
    grid = ((d + pad_r) // br, pl.cdiv(p, block_d))

    def avg(a, b):
        return pl.pallas_call(
            _pair_average_kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
            grid=grid,
            in_specs=[pl.BlockSpec((br, block_d), lambda i, j: (i, j)),
                      pl.BlockSpec((br, block_d), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((br, block_d), lambda i, j: (i, j)),
            interpret=interpret,
        )(a, b)

    s = survive.astype(jnp.float32)[:, None]
    eff = (s * x_new.astype(jnp.float32)
           + (1.0 - s) * x_old.astype(jnp.float32))
    # pad ONCE around the whole stage loop (padded rows self-average and
    # stay zero: perms only address rows < d, extended with the identity)
    if pad_r:
        eff = jnp.pad(eff, ((0, pad_r), (0, 0)))
    tail = jnp.arange(d, d + pad_r, dtype=perms.dtype)
    for i in range(perms.shape[0]):
        perm_p = jnp.concatenate([perms[i], tail])
        eff = avg(eff, jnp.take(eff, perm_p, axis=0))
    return eff[:d].astype(x_new.dtype)
