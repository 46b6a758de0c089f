"""Mamba-2 SSD (state-space duality, arXiv:2405.21060) mixer.

Forward uses the chunked SSD algorithm: quadratic attention-like math inside
chunks (MXU-friendly) + a sequential inter-chunk state recurrence. Decode is
the O(1) recurrent update. The chunked einsums are the oracle for the Pallas
``ssd_scan`` kernel.

The module is dimension-parametric so the hybrid (Hymba) architecture reuses
it for its SSM heads. With ``ngroups`` > 1 (Nemotron-H), each group of
heads shares one B and one C, and the gated RMSNorm normalizes each group
of ``d_inner / ngroups`` channels on its own.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rms_normalize


@dataclass(frozen=True)
class SSMDims:
    d_model: int
    d_inner: int
    nheads: int
    headdim: int
    nstate: int
    conv_width: int = 4
    chunk: int = 256
    ngroups: int = 1
    norm_eps: float = 1e-6

    @property
    def bc_width(self) -> int:
        return self.ngroups * self.nstate

    @property
    def conv_ch(self) -> int:
        return self.d_inner + 2 * self.bc_width


def ssm_dims(cfg) -> SSMDims:
    d_inner = cfg.d_inner_ssm
    return SSMDims(d_model=cfg.d_model, d_inner=d_inner,
                   nheads=d_inner // cfg.ssm_head_dim, headdim=cfg.ssm_head_dim,
                   nstate=cfg.ssm_state, conv_width=cfg.ssm_conv_width,
                   chunk=cfg.ssm_chunk, ngroups=cfg.ssm_n_groups,
                   norm_eps=cfg.ssm_norm_eps)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_ssm(key, dims: SSMDims, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    d_in, h = dims.d_inner, dims.nheads
    proj_out = 2 * d_in + 2 * dims.bc_width + h      # z, x, B, C, dt
    # A in [-1, -e]; dt bias gives softplus(dt) around [1e-3, 1e-1]
    a = jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32,
                                   jnp.log(1.0), jnp.log(4.0)))
    dt0 = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32,
                                     jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": dense_init(ks[0], dims.d_model, (dims.d_model, proj_out), dtype),
        "conv_w": (jax.random.normal(ks[1], (dims.conv_width, dims.conv_ch),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((dims.conv_ch,), dtype),
        "A_log": jnp.log(a),                                  # fp32
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), dtype),
        "out_proj": dense_init(ks[0], d_in, (d_in, dims.d_model), dtype),
    }


def _split_proj(p, x, dims: SSMDims):
    zxbcdt = x @ p["in_proj"]
    d_in, n = dims.d_inner, dims.bc_width
    z, xc, Bm, Cm, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + n, 2 * d_in + 2 * n], axis=-1)
    return z, jnp.concatenate([xc, Bm, Cm], axis=-1), dt      # conv input packed


def _causal_conv(p, u: jnp.ndarray, dims: SSMDims) -> jnp.ndarray:
    """Depthwise causal conv via shifted adds (width <= 4). u: [B,S,ch]."""
    w = p["conv_w"].astype(u.dtype)
    out = jnp.zeros_like(u)
    W = dims.conv_width
    for i in range(W):
        shift = W - 1 - i
        shifted = u if shift == 0 else jnp.pad(u, ((0, 0), (shift, 0), (0, 0)))[:, :-shift]
        out = out + shifted * w[i]
    return jax.nn.silu(out + p["conv_b"].astype(u.dtype))


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """[..., T] -> [..., T, T] lower-triangular segment sums (diag incl.)."""
    T = x.shape[-1]
    c = jnp.cumsum(x, axis=-1)
    z = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, z, -jnp.inf)


# ---------------------------------------------------------------------------
# Chunked SSD core (oracle for kernels/ssd_scan)
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, chunk: int,
                initial_state: Optional[jnp.ndarray] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD: y[t] = C_t . h_t,  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t.

    x: [b,S,h,p], dt: [b,S,h] (post-softplus), A: [h] (negative),
    B, C: [b,S,n] shared by all heads, or [b,S,g,n] per group of h/g
    consecutive heads. Returns (y [b,S,h,p], final_state [b,h,p,n]).
    """
    if B.ndim == 4:
        return _ssd_chunked_grouped(x, dt, A, B, C, chunk, initial_state)
    b, S, h, p = x.shape
    n = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    q = chunk
    nc = S // q
    f32 = jnp.float32

    xd = (x * dt[..., None]).astype(f32).reshape(b, nc, q, h, p)
    A_dt = (dt * A[None, None, :]).astype(f32).reshape(b, nc, q, h)
    A_dt = jnp.transpose(A_dt, (0, 3, 1, 2))                  # [b,h,c,q]
    Bc = B.astype(f32).reshape(b, nc, q, n)
    Cc = C.astype(f32).reshape(b, nc, q, n)

    A_cum = jnp.cumsum(A_dt, axis=-1)                         # [b,h,c,q]
    L = jnp.exp(_segsum(A_dt))                                # [b,h,c,q,q]
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xd)

    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)           # [b,h,c,q]
    states = jnp.einsum("bcsn,bhcs,bcshp->bchpn", Bc, decay_states, xd)
    chunk_decay = jnp.exp(A_cum[..., -1])                     # [b,h,c]

    s0 = (jnp.zeros((b, h, p, n), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(s, inp):
        st_c, dec_c = inp                                     # [b,h,p,n], [b,h]
        s_out = s                                             # state ENTERING chunk
        s_next = s * dec_c[..., None, None] + st_c
        return s_next, s_out

    states_seq = jnp.moveaxis(states, 1, 0)                   # [c,b,h,p,n]
    decay_seq = jnp.moveaxis(chunk_decay, 2, 0)               # [c,b,h]
    final_state, states_in = jax.lax.scan(step, s0, (states_seq, decay_seq))
    states_in = jnp.moveaxis(states_in, 0, 1)                 # [b,c,h,p,n]

    state_decay = jnp.exp(A_cum)                              # [b,h,c,q]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states_in, state_decay)
    y = (y_diag + y_off).reshape(b, S, h, p)
    return y.astype(x.dtype), final_state


def _ssd_chunked_grouped(x, dt, A, B, C, chunk: int, initial_state):
    """``ssd_chunked`` with B, C [b,S,g,n]: the same chunked algorithm with
    the head axis split as (g, r), r = h/g heads per group, so the C.B
    scores are formed once per group."""
    b, S, h, p = x.shape
    g, n = B.shape[-2:]
    r = h // g
    q = chunk
    nc = S // q
    f32 = jnp.float32

    xd = (x * dt[..., None]).astype(f32).reshape(b, nc, q, g, r, p)
    A_dt = (dt * A[None, None, :]).astype(f32).reshape(b, nc, q, g, r)
    A_dt = jnp.transpose(A_dt, (0, 3, 4, 1, 2))               # [b,g,r,c,q]
    Bc = B.astype(f32).reshape(b, nc, q, g, n)
    Cc = C.astype(f32).reshape(b, nc, q, g, n)

    A_cum = jnp.cumsum(A_dt, axis=-1)
    L = jnp.exp(_segsum(A_dt))                                # [b,g,r,c,q,q]
    scores = jnp.einsum("bclgn,bcsgn->bgcls", Cc, Bc)
    y_diag = jnp.einsum("bgcls,bgrcls,bcsgrp->bclgrp", scores, L, xd)

    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)           # [b,g,r,c,q]
    states = jnp.einsum("bcsgn,bgrcs,bcsgrp->bcgrpn", Bc, decay_states, xd)
    chunk_decay = jnp.exp(A_cum[..., -1])                     # [b,g,r,c]

    s0 = (jnp.zeros((b, g, r, p, n), f32) if initial_state is None
          else initial_state.astype(f32).reshape(b, g, r, p, n))

    def step(s, inp):
        st_c, dec_c = inp
        return s * dec_c[..., None, None] + st_c, s

    final_state, states_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 3, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)                 # [b,c,g,r,p,n]

    y_off = jnp.einsum("bclgn,bcgrpn,bgrcl->bclgrp", Cc, states_in,
                       jnp.exp(A_cum))
    y = (y_diag + y_off).reshape(b, S, h, p)
    return y.astype(x.dtype), final_state.reshape(b, h, p, n)


def _per_head(M: jnp.ndarray, nheads: int) -> jnp.ndarray:
    """[b, g, n] group rows -> [b, h, n], each head reading its group's."""
    return jnp.repeat(M, nheads // M.shape[1], axis=1)


def ssd_decode_step(state, x, dt, A, B, C):
    """One-token recurrence. state: [b,h,p,n]; x: [b,h,p]; dt: [b,h];
    B, C: [b,n] shared, or [b,g,n] per group. Returns (y [b,h,p],
    new_state)."""
    f32 = jnp.float32
    decay = jnp.exp((dt * A[None]).astype(f32))               # [b,h]
    xd = (x * dt[..., None]).astype(f32)
    if B.ndim == 3:
        h = x.shape[1]
        Bh, Ch = _per_head(B, h).astype(f32), _per_head(C, h).astype(f32)
        new_state = (state * decay[..., None, None]
                     + jnp.einsum("bhp,bhn->bhpn", xd, Bh))
        y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
        return y.astype(x.dtype), new_state
    upd = jnp.einsum("bhp,bn->bhpn", xd, B.astype(f32))
    new_state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, C.astype(f32))
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Full mixer block (in_proj -> conv -> SSD -> gated norm -> out_proj)
# ---------------------------------------------------------------------------

def init_ssm_cache(batch: int, dims: SSMDims, dtype) -> Dict:
    return {
        "conv": jnp.zeros((batch, dims.conv_width - 1, dims.conv_ch), dtype),
        "state": jnp.zeros((batch, dims.nheads, dims.headdim, dims.nstate),
                           jnp.float32),
    }


def ssm_mixer(p: Dict, x: jnp.ndarray, dims: SSMDims, *,
              cache: Optional[Dict] = None,
              ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x: [B,S,d_model] -> [B,S,d_model]. S==1 with cache => decode."""
    B_, S, _ = x.shape
    h, pdim, n, g = dims.nheads, dims.headdim, dims.bc_width, dims.ngroups
    z, conv_in, dt_raw = _split_proj(p, x, dims)
    A = -jnp.exp(p["A_log"])                                  # [h] negative
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    if cache is not None and S == 1:
        full = jnp.concatenate([cache["conv"], conv_in], axis=1)
        w = p["conv_w"].astype(x.dtype)
        u = jax.nn.silu(jnp.einsum("bwc,wc->bc", full, w) +
                        p["conv_b"].astype(x.dtype))          # [B,ch]
        new_conv = full[:, 1:]
        xc, Bm, Cm = jnp.split(u, [dims.d_inner, dims.d_inner + n], axis=-1)
        if g > 1:
            Bm, Cm = (m.reshape(B_, g, dims.nstate) for m in (Bm, Cm))
        xh = xc.reshape(B_, h, pdim)
        y, new_state = ssd_decode_step(cache["state"], xh, dt[:, 0], A, Bm, Cm)
        y = y + p["D"].astype(y.dtype)[None, :, None] * xh
        y = y.reshape(B_, 1, dims.d_inner)
        cache = {"conv": new_conv, "state": new_state}
    else:
        u = _causal_conv(p, conv_in, dims)                    # [B,S,ch]
        xc, Bm, Cm = jnp.split(u, [dims.d_inner, dims.d_inner + n], axis=-1)
        if g > 1:
            Bm, Cm = (m.reshape(B_, S, g, dims.nstate) for m in (Bm, Cm))
        xh = xc.reshape(B_, S, h, pdim)
        init_state = cache["state"] if cache is not None else None
        chunk = min(dims.chunk, S)
        while S % chunk:                                      # largest divisor
            chunk -= 1
        y, final_state = ssd_chunked(xh, dt, A, Bm, Cm, chunk,
                                     initial_state=init_state)
        y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh
        y = y.reshape(B_, S, dims.d_inner)
        if cache is not None:                                 # prefill
            cache = {"conv": conv_in[:, -(dims.conv_width - 1):],
                     "state": final_state}

    y = y * jax.nn.silu(z)
    if g > 1:                                # RMSNorm per group of channels
        y = y.reshape(y.shape[:-1] + (g, dims.d_inner // g))
        y = rms_normalize(y, jnp.ones((), y.dtype), dims.norm_eps)
        y = y.reshape(y.shape[:-2] + (dims.d_inner,)) * p["norm_scale"]
    else:
        y = rms_normalize(y, p["norm_scale"], dims.norm_eps)
    return y @ p["out_proj"], cache
