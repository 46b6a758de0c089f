"""Attention: GQA/MQA/MHA with RoPE, qk-norm, sliding windows, meta-token
pinning, cross-attention, and full / ring-buffer KV caches.

Long sequences use a blocked online-softmax (flash-style) pure-jnp path so the
lowered HLO never materializes an [S, T] score matrix — this is also the
oracle the Pallas flash kernel is validated against.

Cache layout is owned by ``transformer.py``: buffers for all layers are
stacked ``[L, ...]`` and scanned; this module's functions operate on a single
layer's buffers. ``window``/``num_meta`` may be Python ints or traced scalars
(the hybrid arch selects full-vs-window attention per layer inside the scan),
so masking is branch-free arithmetic.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_normalize

NEG_INF = -1e30
_BIG = jnp.int32(2 ** 30)


# ---------------------------------------------------------------------------
# Masking (branch-free; window/num_meta may be traced)
# ---------------------------------------------------------------------------

def mask_block(q_pos: jnp.ndarray, kv_pos: jnp.ndarray,
               window=0, num_meta=0) -> jnp.ndarray:
    """[Sq, Tk] visibility. window<=0 => full causal. kv slots with pos < 0
    are empty. kv positions < num_meta are always visible (pinned meta)."""
    q = q_pos[:, None].astype(jnp.int32)
    k = kv_pos[None, :].astype(jnp.int32)
    w = jnp.asarray(window, jnp.int32)
    m = jnp.asarray(num_meta, jnp.int32)
    eff_w = jnp.where(w > 0, w, _BIG)
    visible = ((q - k) < eff_w) | (k < m)
    return (k >= 0) & (k <= q) & visible


# ---------------------------------------------------------------------------
# Attention cores (q grouped for GQA: [B,S,Hk,G,hd])
# ---------------------------------------------------------------------------

def _direct_attention(q, k, v, q_pos, kv_pos, window, num_meta) -> jnp.ndarray:
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k).astype(jnp.float32) * scale
    mask = mask_block(q_pos, kv_pos, window, num_meta)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgst,bthv->bshgv", probs, v)


def _pick_block(n: int, target: int) -> int:
    b = min(n, target)
    while n % b:
        b -= 1
    return b


def _flash_fwd_impl(q, k, v, q_pos, kv_pos, window, num_meta,
                    q_block: int, k_block: int):
    """Returns (out [B,Sq,Hk,G,vd], lse [B,Hk,G,Sq])."""
    B, Sq, Hk, G, hd = q.shape
    Tk = k.shape[1]
    vd = v.shape[-1]
    qb = _pick_block(Sq, q_block)
    kb = _pick_block(Tk, k_block)
    scale = hd ** -0.5

    q_chunks = q.reshape(B, Sq // qb, qb, Hk, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qpos_chunks = q_pos.reshape(Sq // qb, qb)
    k_chunks = k.reshape(B, Tk // kb, kb, Hk, hd).transpose(1, 0, 2, 3, 4)
    v_chunks = v.reshape(B, Tk // kb, kb, Hk, vd).transpose(1, 0, 2, 3, 4)
    kpos_chunks = kv_pos.reshape(Tk // kb, kb)

    def one_q_chunk(_, qc):
        qi, qp = qc                                   # [B,qb,Hk,G,hd], [qb]

        def inner(carry, kc):
            m, d, acc = carry
            ki, vi, kp = kc
            s = jnp.einsum("bshgd,bthd->bhgst", qi, ki).astype(jnp.float32) * scale
            msk = mask_block(qp, kp, window, num_meta)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            d_new = d * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgst,bthv->bhgsv", p.astype(vi.dtype), vi).astype(jnp.float32)
            return (m_new, d_new, acc_new), None

        m0 = jnp.full((B, Hk, G, qb), NEG_INF, jnp.float32)
        d0 = jnp.zeros((B, Hk, G, qb), jnp.float32)
        a0 = jnp.zeros((B, Hk, G, qb, vd), jnp.float32)
        (m, d, acc), _ = jax.lax.scan(inner, (m0, d0, a0),
                                      (k_chunks, v_chunks, kpos_chunks))
        out = acc / jnp.maximum(d[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(d, 1e-30))      # [B,Hk,G,qb]
        return None, (out.transpose(0, 3, 1, 2, 4), lse)

    _, (outs, lses) = jax.lax.scan(one_q_chunk, None, (q_chunks, qpos_chunks))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hk, G, vd).astype(v.dtype)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, Hk, G, Sq)
    return out, lse


# custom VJP: the backward recomputes P blockwise from (q, k, v, lse) — the
# flash-attention trick — so training never stores per-block softmax
# residuals. Mask parameters cross the boundary as float arrays (int/traced
# values can't be nondiff_argnums when they come from a scanned layer).

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _flash(q, k, v, q_posf, kv_posf, windowf, num_metaf,
           q_block: int, k_block: int):
    out, _ = _flash_fwd_impl(q, k, v, q_posf.astype(jnp.int32),
                             kv_posf.astype(jnp.int32),
                             windowf.astype(jnp.int32),
                             num_metaf.astype(jnp.int32), q_block, k_block)
    return out


def _flash_vjp_fwd(q, k, v, q_posf, kv_posf, windowf, num_metaf,
                   q_block, k_block):
    out, lse = _flash_fwd_impl(q, k, v, q_posf.astype(jnp.int32),
                               kv_posf.astype(jnp.int32),
                               windowf.astype(jnp.int32),
                               num_metaf.astype(jnp.int32), q_block, k_block)
    return out, (q, k, v, out, lse, q_posf, kv_posf, windowf, num_metaf)


def _flash_vjp_bwd(q_block, k_block, res, do):
    q, k, v, out, lse, q_posf, kv_posf, windowf, num_metaf = res
    q_pos = q_posf.astype(jnp.int32)
    kv_pos = kv_posf.astype(jnp.int32)
    window = windowf.astype(jnp.int32)
    num_meta = num_metaf.astype(jnp.int32)
    B, Sq, Hk, G, hd = q.shape
    Tk = k.shape[1]
    vd = v.shape[-1]
    qb = _pick_block(Sq, q_block)
    kb = _pick_block(Tk, k_block)
    scale = hd ** -0.5
    f32 = jnp.float32

    delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1)     # [B,Sq,Hk,G]
    delta = delta.transpose(0, 2, 3, 1)                            # [B,Hk,G,Sq]

    qch = q.reshape(B, Sq // qb, qb, Hk, G, hd).transpose(1, 0, 2, 3, 4, 5)
    doch = do.reshape(B, Sq // qb, qb, Hk, G, vd).transpose(1, 0, 2, 3, 4, 5)
    lch = lse.reshape(B, Hk, G, Sq // qb, qb).transpose(3, 0, 1, 2, 4)
    dch = delta.reshape(B, Hk, G, Sq // qb, qb).transpose(3, 0, 1, 2, 4)
    qpch = q_pos.reshape(Sq // qb, qb)
    kch = k.reshape(B, Tk // kb, kb, Hk, hd).transpose(1, 0, 2, 3, 4)
    vch = v.reshape(B, Tk // kb, kb, Hk, vd).transpose(1, 0, 2, 3, 4)
    kpch = kv_pos.reshape(Tk // kb, kb)

    def over_kv(dq_acc, kc):
        kj, vj, kp = kc

        def over_q(carry, qc):
            dkj, dvj, dq_acc = carry
            qi, doi, lsei, deli, qp, iq = qc
            s = jnp.einsum("bshgd,bthd->bhgst", qi, kj).astype(f32) * scale
            msk = mask_block(qp, kp, window, num_meta)[None, None, None]
            p = jnp.where(msk, jnp.exp(s - lsei[..., None]), 0.0)
            dvj = dvj + jnp.einsum("bhgst,bshgv->bthv", p, doi.astype(f32))
            dp = jnp.einsum("bshgv,bthv->bhgst", doi.astype(f32), vj.astype(f32))
            ds = p * (dp - deli[..., None]) * scale
            dqi = jnp.einsum("bhgst,bthd->bshgd", ds, kj.astype(f32))
            dkj = dkj + jnp.einsum("bhgst,bshgd->bthd", ds, qi.astype(f32))
            prev = jax.lax.dynamic_slice(
                dq_acc, (0, iq * qb, 0, 0, 0), (B, qb, Hk, G, hd))
            dq_acc = jax.lax.dynamic_update_slice(
                dq_acc, prev + dqi.astype(dq_acc.dtype), (0, iq * qb, 0, 0, 0))
            return (dkj, dvj, dq_acc), None

        dk0 = jnp.zeros((B, kb, Hk, hd), f32)
        dv0 = jnp.zeros((B, kb, Hk, vd), f32)
        (dkj, dvj, dq_acc), _ = jax.lax.scan(
            over_q, (dk0, dv0, dq_acc),
            (qch, doch, lch, dch, qpch, jnp.arange(Sq // qb)))
        return dq_acc, (dkj, dvj)

    dq0 = jnp.zeros((B, Sq, Hk, G, hd), f32)
    dq, (dks, dvs) = jax.lax.scan(over_kv, dq0, (kch, vch, kpch))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Tk, Hk, hd)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Tk, Hk, vd)
    zeros = (jnp.zeros_like(q_posf), jnp.zeros_like(kv_posf),
             jnp.zeros_like(windowf), jnp.zeros_like(num_metaf))
    # dq accumulated ADDITIVELY across kv chunks above via dynamic updates of
    # disjoint q slices per inner step — each (iq) slice is written once per
    # kv chunk; accumulate by adding the new contribution to the carry.
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)) + zeros


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def blocked_attention(q, k, v, q_pos, kv_pos, window=0, num_meta=0,
                      q_block: int = 1024, k_block: int = 1024) -> jnp.ndarray:
    """Flash-style attention with memory-safe custom VJP.

    q: [B,Sq,Hk,G,hd], k: [B,Tk,Hk,hd], v: [B,Tk,Hk,vd] -> [B,Sq,Hk,G,vd].
    """
    return _flash(q, k, v,
                  jnp.asarray(q_pos, jnp.float32),
                  jnp.asarray(kv_pos, jnp.float32),
                  jnp.asarray(window, jnp.float32),
                  jnp.asarray(num_meta, jnp.float32),
                  q_block, k_block)


#: the direct path's float32 score tensor may take at most this many bytes
DIRECT_SCORE_BYTES = 2 ** 30


def attention_core(q, k, v, q_pos, kv_pos, window=0, num_meta=0) -> jnp.ndarray:
    """Static dispatch: blocked for long q, or where the [B, H, Sq, Tk]
    float32 scores would pass ``DIRECT_SCORE_BYTES``; dense otherwise."""
    B, Sq, Hk, G, _ = q.shape
    if Sq >= 4096 or 4 * B * Hk * G * Sq * k.shape[1] > DIRECT_SCORE_BYTES:
        return blocked_attention(q, k, v, q_pos, kv_pos, window, num_meta)
    return _direct_attention(q, k, v, q_pos, kv_pos, window, num_meta)


# ---------------------------------------------------------------------------
# Ring-buffer slot addressing (shared by standard and MLA caches)
# ---------------------------------------------------------------------------

def cache_write_slot(buf_len: int, index, num_meta) -> jnp.ndarray:
    """Ring addressing with the first ``num_meta`` slots pinned. Positions
    < num_meta map to their own slot; later positions ring over the rest.
    For a full cache (buf_len >= total length) this is the identity."""
    index = jnp.asarray(index, jnp.int32)
    m = jnp.asarray(num_meta, jnp.int32)
    ring = jnp.maximum(buf_len - m, 1)
    return jnp.where(index < buf_len,
                     jnp.where(index < m, index, m + (index - m) % ring),
                     m + (index - m) % ring).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Standard (non-MLA) attention block
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> Dict:
    hq, hk, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, (d, hq * hd), dtype),
        "wk": dense_init(ks[1], d, (d, hk * hd), dtype),
        "wv": dense_init(ks[2], d, (d, hk * hd), dtype),
        "wo": dense_init(ks[3], hq * hd, (hq * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hk * hd,), dtype)
        p["bv"] = jnp.zeros((hk * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p: Dict, x: jnp.ndarray, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    from repro.sharding import shard
    q = shard(x @ p["wq"], "act_q")
    k = shard(x @ p["wk"], "act_kv")
    v = shard(x @ p["wv"], "act_kv")
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, hq, hd)
    k = k.reshape(B, S, hk, hd)
    v = v.reshape(B, S, hk, hd)
    if cfg.qk_norm:
        q = rms_normalize(q, p["q_norm"])
        k = rms_normalize(k, p["k_norm"])
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    return q, k, v


def pos1d(positions: jnp.ndarray) -> jnp.ndarray:
    """[B,S] (shared across batch) or [S] -> [S] for mask math."""
    return positions[0] if positions.ndim == 2 else positions.reshape(-1)


def attention(p: Dict, x: jnp.ndarray, cfg: ModelConfig, *,
              positions: jnp.ndarray, window=0, num_meta=0,
              kv_bufs: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              kv_pos: Optional[jnp.ndarray] = None,
              write_slot: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """One layer of self-attention.

    Train (no cache):      kv_bufs is None.
    Prefill (fill cache):  kv_bufs given, S > 1, write_slot None -> write [0:S).
    Decode (one token):    kv_bufs given, S == 1, write_slot = ring slot.
    kv_pos: absolute position per cache slot AFTER this step's write (-1 empty).
    """
    B, S, _ = x.shape
    hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = hq // hk
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = q.reshape(B, S, hk, G, hd)

    new_bufs = None
    if kv_bufs is None:
        pos_flat = pos1d(positions)
        out = attention_core(q, k, v, pos_flat, pos_flat, window, num_meta)
    else:
        k_buf, v_buf = kv_bufs
        if S == 1:
            k_buf = jax.lax.dynamic_update_slice(k_buf, k, (0, write_slot, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(v_buf, v, (0, write_slot, 0, 0))
            out = attention_core(q, k_buf, v_buf, positions[:1, 0],
                                 kv_pos, window, num_meta)
        else:                                        # prefill
            k_buf = jax.lax.dynamic_update_slice(k_buf, k, (0, 0, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(v_buf, v, (0, 0, 0, 0))
            pos_flat = pos1d(positions)
            out = attention_core(q, k, v, pos_flat, pos_flat, window, num_meta)
        new_bufs = (k_buf, v_buf)

    y = out.reshape(B, S, hq * hd) @ p["wo"]
    return y, new_bufs


# ---------------------------------------------------------------------------
# Cross-attention (musicgen conditioning)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ModelConfig, dtype) -> Dict:
    hq, hd, d = cfg.num_heads, cfg.head_dim, cfg.d_model
    cd = cfg.cross_context_dim or d
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, (d, hq * hd), dtype),
        "wk": dense_init(ks[1], cd, (cd, hq * hd), dtype),
        "wv": dense_init(ks[2], cd, (cd, hq * hd), dtype),
        "wo": dense_init(ks[3], hq * hd, (hq * hd, d), dtype),
    }


def cross_attention(p: Dict, x: jnp.ndarray, cfg: ModelConfig, *,
                    context: Optional[jnp.ndarray] = None,
                    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                    ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Either ``context`` [B,Tc,cd] (train/prefill — K/V computed and
    returned for caching) or precomputed ``cross_kv`` (decode)."""
    B, S, _ = x.shape
    hq, hd = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, hq, hd)
    if cross_kv is None:
        Tc = context.shape[1]
        k = (context @ p["wk"]).reshape(B, Tc, hq, hd)
        v = (context @ p["wv"]).reshape(B, Tc, hq, hd)
    else:
        k, v = cross_kv
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * hd ** -0.5
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out.reshape(B, S, hq * hd) @ p["wo"], (k, v)
