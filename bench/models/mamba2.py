"""Mamba-2 (arXiv:2405.21060) as a plain language model: weights in the
program's layout, the float reference loss, and the operations one token
needs.

Per layer: RMSNorm, ``in_proj`` to (z, x, B, C, dt), a depthwise causal
convolution with SiLU over (x, B, C), the SSD recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
(one B/C group shared by all heads), a gated RMSNorm ``norm(y * silu(z))``
and ``out_proj``, added to the residual. A final RMSNorm and logits tied to
the embedding table; the loss is the mean cross-entropy over every token.
The recurrence is evaluated chunk by chunk in its exact quadratic form
inside a chunk and carried as a state between chunks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    d_inner = cfg["ssm_expand"] * cfg["d_model"]
    heads = d_inner // cfg["ssm_head_dim"]
    n = cfg["ssm_state"]
    return {"d": cfg["d_model"], "d_inner": d_inner, "heads": heads,
            "p": cfg["ssm_head_dim"], "n": n, "conv": cfg["ssm_conv_width"],
            "conv_ch": d_inner + 2 * n, "proj": 2 * d_inner + 2 * n + heads}


def init(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree: ``embed.table``, stacked
    ``layers.{ln1.scale, ssm.*}`` and ``ln_f.scale``."""
    m, L = dims(cfg), cfg["num_layers"]
    ks = jax.random.split(key, 7)

    def trunc(k, fan_in, shape):
        return (jax.random.truncated_normal(k, -3.0, 3.0, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    a = jnp.exp(jax.random.uniform(ks[3], (L, m["heads"]), jnp.float32,
                                   0.0, jnp.log(4.0)))
    dt0 = jnp.exp(jax.random.uniform(ks[4], (L, m["heads"]), jnp.float32,
                                     jnp.log(1e-3), jnp.log(1e-1)))
    ssm = {
        "in_proj": trunc(ks[0], m["d"], (L, m["d"], m["proj"])),
        "conv_w": (0.1 * jax.random.normal(ks[1], (L, m["conv"], m["conv_ch"]),
                                           jnp.float32)).astype(dtype),
        "conv_b": jnp.zeros((L, m["conv_ch"]), dtype),
        "A_log": jnp.log(a),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "D": jnp.ones((L, m["heads"]), jnp.float32),
        "norm_scale": jnp.ones((L, m["d_inner"]), dtype),
        "out_proj": trunc(ks[2], m["d_inner"], (L, m["d_inner"], m["d"])),
    }
    return {
        "embed": {"table": (0.02 * jax.random.normal(
            ks[5], (cfg["vocab_size"], m["d"]), jnp.float32)).astype(dtype)},
        "layers": {"ln1": {"scale": jnp.ones((L, m["d"]), dtype)}, "ssm": ssm},
        "ln_f": {"scale": jnp.ones((m["d"],), dtype)},
    }


def _rmsnorm(x, scale, eps):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(ms + eps).astype(x.dtype)) * scale.astype(x.dtype)


def _causal_conv(u, w, b):
    """u [B, S, ch]; w [W, ch]: out_t = sum_i w_i u_{t - (W - 1 - i)}."""
    W, S = w.shape[0], u.shape[1]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + S] * w[i].astype(u.dtype) for i in range(W))
    return jax.nn.silu(out + b.astype(u.dtype))


def ssd(x, dt, A, B, C, chunk: int):
    """x [b, S, h, p], dt [b, S, h], A [h], B/C [b, S, n] -> y [b, S, h, p]."""
    b, S, h, p = x.shape
    n = B.shape[-1]
    q = chunk
    c = S // q
    f32 = jnp.float32
    xd = (x * dt[..., None]).astype(f32).reshape(b, c, q, h, p)
    la = (dt * A).astype(f32).reshape(b, c, q, h)          # log decay per step
    cum = jnp.cumsum(la, axis=2)                            # [b, c, q, h]
    Bc = B.astype(f32).reshape(b, c, q, n)
    Cc = C.astype(f32).reshape(b, c, q, n)
    # within a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(cum_l - cum_s) xd_s
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # [b, c, l, s, h]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    scores = jnp.einsum("bcln,bcsn->bcls", Cc, Bc)
    y = jnp.einsum("bcls,bclsh,bcshp->bclhp", scores, decay, xd)
    # each chunk's own end state, then the states carried between chunks
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)               # [b, c, q, h]
    own = jnp.einsum("bcsn,bcsh,bcshp->bchpn", Bc, to_end, xd)
    whole = jnp.exp(cum[:, :, -1, :])                       # [b, c, h]

    def carry(state, inp):
        own_c, whole_c = inp
        return state * whole_c[..., None, None] + own_c, state

    _, entering = jax.lax.scan(carry, jnp.zeros((b, h, p, n), f32),
                               (jnp.moveaxis(own, 1, 0),
                                jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # [b, c, h, p, n]
    y = y + jnp.einsum("bcln,bchpn,bclh->bclhp", Cc, entering, jnp.exp(cum))
    return y.reshape(b, S, h, p).astype(x.dtype)


def _layer(cfg, m, x, lp):
    h = _rmsnorm(x, lp["ln1"]["scale"], cfg["norm_eps"])
    p = lp["ssm"]
    proj = h @ p["in_proj"].astype(h.dtype)
    d_in, n = m["d_inner"], m["n"]
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * n], axis=-1)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xs, B, C = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    bsz, S = x.shape[:2]
    xs = xs.reshape(bsz, S, m["heads"], m["p"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    chunk = min(cfg["ssm_chunk"], S)
    y = ssd(xs, dt, A, B, C, chunk)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xs
    y = y.reshape(bsz, S, d_in)
    y = _rmsnorm(y * jax.nn.silu(z), p["norm_scale"], 1e-6)
    return x + y @ p["out_proj"].astype(y.dtype)


def loss(params, tokens, labels, cfg: dict, ce_chunk: int = 256):
    """Mean next-token cross-entropy; layers and logit chunks are
    rematerialized so the reference fits beside nothing else."""
    m = dims(cfg)
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0)

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: _layer(cfg, m, x, lp))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg["norm_eps"])
    bsz, S, d = x.shape
    cs = min(ce_chunk, S)
    hc = jnp.moveaxis(x.reshape(bsz, S // cs, cs, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(bsz, S // cs, cs), 1, 0)

    @jax.checkpoint
    def chunk_nll(h, lab):
        logits = (h @ table.T.astype(h.dtype)).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    def ce(tot, xs):
        return tot + chunk_nll(*xs), None

    total, _ = jax.lax.scan(ce, jnp.zeros((), jnp.float32), (hc, lc))
    return total / labels.size


def forward_flops_per_token(cfg: dict, seq: int) -> int:
    """Multiply-adds x 2 of one token's forward pass at sequence length
    ``seq``: the projections, the tied logits, and the SSD in its chunked
    form (C.B scores, the score-weighted sum of inputs, each chunk's own end
    state, and the entering state read out by C). The embedding gather, the
    convolution and the norms are not counted."""
    m, L = dims(cfg), cfg["num_layers"]
    q = min(cfg["ssm_chunk"], seq)
    h, p, n = m["heads"], m["p"], m["n"]
    proj = m["d"] * m["proj"] + m["d_inner"] * m["d"]
    ssd_ops = q * n + q * h * p + 2 * h * p * n
    return 2 * (L * (proj + ssd_ops) + cfg["vocab_size"] * m["d"])


def train_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward and backward: three times the forward; recomputation is not
    counted."""
    return 3 * forward_flops_per_token(cfg, seq)
