"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

These intentionally re-derive the math independently (dense forms) rather
than re-using the blocked model-code paths, so kernel tests pin both the
kernels AND the blocked jnp implementations to one dense reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import F32_CONTRACT


def fed_aggregate_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x: [N, D]; w: [N] -> [D] (f32 accumulate, cast back)."""
    out = jnp.einsum("n,nd->d", w.astype(jnp.float32), x.astype(jnp.float32),
                     precision=F32_CONTRACT)
    return out.astype(x.dtype)


def fed_mix_ref(m_new: jnp.ndarray, m_old: jnp.ndarray,
                x_new: jnp.ndarray, x_old: jnp.ndarray) -> jnp.ndarray:
    """m_new, m_old: [D, D]; x_new, x_old: [D, P] -> [D, P].

    The dense mixing operator f_out = M_new @ f_new + M_old @ f_old on
    flat-packed client params (f32 accumulate, cast back to x_new.dtype).
    """
    out = jnp.matmul(m_new.astype(jnp.float32), x_new.astype(jnp.float32),
                     precision=F32_CONTRACT)
    out = out + jnp.matmul(m_old.astype(jnp.float32),
                           x_old.astype(jnp.float32), precision=F32_CONTRACT)
    return out.astype(x_new.dtype)


def fed_mix_segment_ref(cluster_ids: jnp.ndarray, w_new: jnp.ndarray,
                        w_old: jnp.ndarray, x_new: jnp.ndarray,
                        x_old: jnp.ndarray, *, num_segments: int
                        ) -> jnp.ndarray:
    """cluster_ids: [D] int32; w_new, w_old: [D]; x_new, x_old: [D, P];
    num_segments: static L -> [D, P].

    The cluster-segment mixing operator in O(D·P) FLOPs: per-cluster sums of
    the weighted rows, gathered back to every member row —

        out_i = sum_{j: c(j)=c(i)} (w_new_j x_new_j + w_old_j x_old_j)

    — the structured form of any block-diagonal ``MixingSpec`` whose rows
    agree within a cluster (FedAvg, FedP2P; L=1 is the global rank-1 term).
    f32 accumulate, cast back to x_new.dtype.
    """
    y = (w_new.astype(jnp.float32)[:, None] * x_new.astype(jnp.float32)
         + w_old.astype(jnp.float32)[:, None] * x_old.astype(jnp.float32))
    seg = jax.ops.segment_sum(y, cluster_ids, num_segments=num_segments)
    return jnp.take(seg, cluster_ids, axis=0).astype(x_new.dtype)


def fed_mix_matching_ref(perms: jnp.ndarray, survive: jnp.ndarray,
                         x_new: jnp.ndarray, x_old: jnp.ndarray
                         ) -> jnp.ndarray:
    """perms: [S, D] int32 stage partner indices (perm[i]=i for byes);
    survive: [D] 0/1; x_new, x_old: [D, P] -> [D, P].

    The pairwise-matching mixing operator in O(S·D·P) work and O(D) index
    memory: stragglers contribute their OLD row (their update "never
    arrived"), then each stage averages every row with its partner row —
    the structured form of gossip's per-round doubly stochastic operator
    (S=2 ring phases; S=1 random matchings). f32 accumulate, cast back.
    """
    s = survive.astype(jnp.float32)[:, None]
    eff = (s * x_new.astype(jnp.float32)
           + (1.0 - s) * x_old.astype(jnp.float32))
    for i in range(perms.shape[0]):
        eff = 0.5 * (eff + jnp.take(eff, perms[i], axis=0))
    return eff.astype(x_new.dtype)


def fed_mix_q_ref(m_new: jnp.ndarray, m_old: jnp.ndarray,
                  q_new: jnp.ndarray, scales: jnp.ndarray,
                  x_old: jnp.ndarray, *, chunk: int = 256,
                  out_dtype=None) -> jnp.ndarray:
    """m_new, m_old: [D, D]; q_new: int8 [D, Pq] (Pq a multiple of chunk);
    scales: f32 [D, Pq/chunk]; x_old: [D, P], P <= Pq -> [D, P].

    The quantized-wire mixing operator: dequantize the int8 record
    (per-chunk absmax scales), then the dense f32 mix. The independent
    correctness contract for ``kernels.fed_mix_q``'s inline dequant.
    """
    d = q_new.shape[0]
    n = x_old.shape[1]
    v = q_new.astype(jnp.float32).reshape(d, -1, chunk)
    xn = (v * scales.astype(jnp.float32)[..., None]).reshape(d, -1)[:, :n]
    out = jnp.matmul(m_new.astype(jnp.float32), xn, precision=F32_CONTRACT)
    out = out + jnp.matmul(m_old.astype(jnp.float32),
                           x_old.astype(jnp.float32), precision=F32_CONTRACT)
    return out.astype(x_old.dtype if out_dtype is None else out_dtype)


def flash_attention_ref(q, k, v, *, window: int = 0) -> jnp.ndarray:
    """q: [B,Hq,Sq,hd]; k, v: [B,Hkv,Tk,hd] -> [B,Hq,Sq,hd]. Dense softmax."""
    b, hq, sq, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * hd ** -0.5
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(tk)[None, :]
    mask = kp <= qp
    if window > 0:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C):
    """Naive sequential SSD recurrence (the ground truth both the chunked jnp
    path and the Pallas kernel must match).
    x [b,S,h,p], dt [b,S,h], A [h], B/C [b,S,n] -> (y, final_state)."""
    b, S, h, p = x.shape
    n = B.shape[-1]
    f32 = jnp.float32

    def step(state, inp):
        xt, dtt, Bt, Ct = inp                       # [b,h,p], [b,h], [b,n], [b,n]
        decay = jnp.exp(dtt * A[None, :])           # [b,h]
        upd = jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], Bt)
        state = state * decay[..., None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", state, Ct)
        return state, y

    xs = (jnp.moveaxis(x.astype(f32), 1, 0),
          jnp.moveaxis(dt.astype(f32), 1, 0),
          jnp.moveaxis(B.astype(f32), 1, 0),
          jnp.moveaxis(C.astype(f32), 1, 0))
    state0 = jnp.zeros((b, h, p, n), f32)
    final, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final
