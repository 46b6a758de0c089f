"""Nemotron-H (arXiv:2504.03624), as NVIDIA-Nemotron-3-Nano-30B-A3B
publishes it, cut to one chip's share: weights in the program's layout, the
float reference loss, and the operations one token needs.

The configuration is read by its published keys (``hidden_size``,
``mamba_num_heads``, ``n_routed_experts``, ...) and cut by three: the
first ``num_layers`` blocks of ``hybrid_override_pattern``, the
``num_experts`` experts held (experts ``[0, num_experts)`` of the
router's ``n_routed_experts``), and a ``vocab_size`` slice.

Each block is ``x + mixer(RMSNorm(x))`` with one mixer:

* "M", Mamba-2: ``in_proj`` to (z, x, B, C, dt) with B and C per group of
  heads, a depthwise causal convolution with bias and SiLU over (x, B, C),
  the SSD recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``,
  ``y_t = C_t h_t + D x_t`` (each head reading its group's B and C), then
  ``RMSNorm(y * silu(z))`` over each group of channels and ``out_proj``.
  The recurrence is evaluated chunk by chunk in its exact quadratic form
  inside a chunk, head by head, and carried as a state between chunks.
* "E", MoE: float32 router logits, sigmoid scores, the top k chosen on
  ``scores + e_score_correction_bias``, weighted by the plain scores
  normalized to sum 1 and times ``routed_scaling_factor``; every held
  expert is a relu^2 MLP (``up``, ``relu(.)^2``, ``down``) evaluated on
  every token and weighted by that token's gate for it (0 where it was not
  chosen): a loop over the held experts, with no dispatch. Experts not
  held add nothing. The shared expert is added for every token.
* "*", attention: GQA with no rotary and no bias, causal, softmax over
  the keys, computed for one block of queries at a time.

A final RMSNorm and an untied head; the loss is the mean cross-entropy
over every token of the vocabulary slice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: the parameter key of each block's mixer, as the program names it
MIXERS = {"M": "ssm", "E": "moe", "*": "attn"}


def dims(cfg: dict) -> dict:
    d, h, p = cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_inner = h * p
    return {"d": d, "d_inner": d_inner, "heads": h, "p": p, "g": g, "n": n,
            "conv": cfg["conv_kernel"], "conv_ch": d_inner + 2 * g * n,
            "proj": 2 * d_inner + 2 * g * n + h,
            "E": cfg["num_experts"], "E_router": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "ff": cfg["moe_intermediate_size"],
            "ff_shared": cfg["moe_shared_expert_intermediate_size"],
            "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
            "hd": cfg["head_dim"], "V": cfg["vocab_size"]}


def pattern(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_layers"]]


def runs(cfg: dict) -> list:
    """[(kind, length)] of the runs of equal blocks (the program stacks
    each run's weights)."""
    out = []
    for c in pattern(cfg):
        if out and out[-1][0] == c:
            out[-1] = (c, out[-1][1] + 1)
        else:
            out.append((c, 1))
    return out


def init(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree: ``embed.{table, unembed}``,
    ``blocks`` (one dict per run, each leaf stacked over the run's blocks:
    ``ln1.scale`` and the mixer's weights) and ``ln_f.scale``. Matrices are
    truncated normal over the square root of their fan-in; the
    ``e_score_correction_bias`` (``moe.router_bias``) starts at 0."""
    m = dims(cfg)
    d = m["d"]
    k_embed, k_head, k_blocks = jax.random.split(key, 3)

    def trunc(k, fan_in, shape):
        return (jax.random.truncated_normal(k, -3.0, 3.0, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    def mixer(kind, k, L):
        ks = jax.random.split(k, 8)
        if kind == "M":
            h = m["heads"]
            a = jnp.exp(jax.random.uniform(ks[3], (L, h), jnp.float32,
                                           0.0, jnp.log(4.0)))
            dt0 = jnp.exp(jax.random.uniform(
                ks[4], (L, h), jnp.float32, jnp.log(cfg["time_step_min"]),
                jnp.log(cfg["time_step_max"])))
            return {
                "in_proj": trunc(ks[0], d, (L, d, m["proj"])),
                "conv_w": (0.1 * jax.random.normal(
                    ks[1], (L, m["conv"], m["conv_ch"]), jnp.float32)
                           ).astype(dtype),
                "conv_b": jnp.zeros((L, m["conv_ch"]), dtype),
                "A_log": jnp.log(a),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "D": jnp.ones((L, h), jnp.float32),
                "norm_scale": jnp.ones((L, m["d_inner"]), dtype),
                "out_proj": trunc(ks[2], m["d_inner"], (L, m["d_inner"], d)),
            }
        if kind == "E":
            E, ff, fs = m["E"], m["ff"], m["ff_shared"]
            return {
                "router": trunc(ks[0], d, (L, d, m["E_router"])).astype(
                    jnp.float32),
                "router_bias": jnp.zeros((L, m["E_router"]), jnp.float32),
                "w_in": trunc(ks[1], d, (L, E, d, ff)),
                "w_out": trunc(ks[2], ff, (L, E, ff, d)),
                "shared": {"w_in": trunc(ks[3], d, (L, d, fs)),
                           "w_out": trunc(ks[4], fs, (L, fs, d))},
            }
        hq, hk, hd = m["hq"], m["hk"], m["hd"]
        return {"wq": trunc(ks[0], d, (L, d, hq * hd)),
                "wk": trunc(ks[1], d, (L, d, hk * hd)),
                "wv": trunc(ks[2], d, (L, d, hk * hd)),
                "wo": trunc(ks[3], hq * hd, (L, hq * hd, d))}

    blocks = []
    for (kind, L), k in zip(runs(cfg), jax.random.split(k_blocks,
                                                        len(runs(cfg)))):
        blocks.append({"ln1": {"scale": jnp.ones((L, d), dtype)},
                       MIXERS[kind]: mixer(kind, k, L)})
    return {
        "embed": {"table": (0.02 * jax.random.normal(
                      k_embed, (m["V"], d), jnp.float32)).astype(dtype),
                  "unembed": trunc(k_head, d, (d, m["V"]))},
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((d,), dtype)},
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
    """x [b, S, h, p], dt [b, S, h], A [h], B/C [b, S, h, n] (each head's
    own rows) -> y [b, S, h, p]."""
    b, S, h, p = x.shape
    n = B.shape[-1]
    q = chunk
    c = S // q
    f32 = jnp.float32
    xd = (x * dt[..., None]).astype(f32).reshape(b, c, q, h, p)
    la = (dt * A).astype(f32).reshape(b, c, q, h)
    cum = jnp.cumsum(la, axis=2)                            # [b, c, q, h]
    Bc = B.astype(f32).reshape(b, c, q, h, n)
    Cc = C.astype(f32).reshape(b, c, q, h, n)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # [b, c, l, s, h]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    scores = jnp.einsum("bclhn,bcshn->bclsh", Cc, Bc)
    y = jnp.einsum("bclsh,bclsh,bcshp->bclhp", scores, decay, xd)
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)               # [b, c, q, h]
    own = jnp.einsum("bcshn,bcsh,bcshp->bchpn", Bc, to_end, xd)
    whole = jnp.exp(cum[:, :, -1, :])                       # [b, c, h]

    def carry(state, inp):
        own_c, whole_c = inp
        return state * whole_c[..., None, None] + own_c, state

    _, entering = jax.lax.scan(carry, jnp.zeros((b, h, p, n), f32),
                               (jnp.moveaxis(own, 1, 0),
                                jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # [b, c, h, p, n]
    y = y + jnp.einsum("bclhn,bchpn,bclh->bclhp", Cc, entering, jnp.exp(cum))
    return y.reshape(b, S, h, p).astype(x.dtype)


def _mamba(cfg, m, h, p):
    bsz, S = h.shape[:2]
    d_in, gn = m["d_inner"], m["g"] * m["n"]
    proj = h @ p["in_proj"].astype(h.dtype)
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * gn], axis=-1)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xs, B, C = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
    xs = xs.reshape(bsz, S, m["heads"], m["p"])
    # head j reads group j // (heads / g)
    group_of = jnp.arange(m["heads"]) // (m["heads"] // m["g"])
    B = B.reshape(bsz, S, m["g"], m["n"])[:, :, group_of]
    C = C.reshape(bsz, S, m["g"], m["n"])[:, :, group_of]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = ssd(xs, dt, A, B, C, min(cfg["chunk_size"], S))
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xs
    y = (y.reshape(bsz, S, d_in) * jax.nn.silu(z)).reshape(
        bsz, S, m["g"], d_in // m["g"])
    y = _rmsnorm(y, jnp.ones((), y.dtype), cfg["layer_norm_epsilon"])
    y = y.reshape(bsz, S, d_in) * p["norm_scale"].astype(y.dtype)
    return y @ p["out_proj"].astype(y.dtype)


def _relu2_mlp(x, w_in, w_out):
    return jnp.square(jax.nn.relu(x @ w_in.astype(x.dtype))) @ w_out.astype(
        x.dtype)


def route(cfg, m, x, router, bias):
    """x [T, d] -> (gates [T, k] float32, chosen experts [T, k])."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), m["k"])
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * cfg["routed_scaling_factor"], idx


def moe(cfg, m, h, p, *, first: int = 0, shared: bool = True):
    """The E mixer's part from the held experts ``[first, first + E)`` of
    the router (plus the shared expert when ``shared``)."""
    bsz, S, d = h.shape
    x = h.reshape(bsz * S, d)
    gates, idx = route(cfg, m, x, p["router"], p["router_bias"])

    @jax.checkpoint
    def one_expert(y, e):
        w_in, w_out, j = e
        gate = jnp.sum(jnp.where(idx == first + j, gates, 0.0), axis=-1)
        return y + gate[:, None].astype(x.dtype) * _relu2_mlp(x, w_in, w_out), \
            None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (p["w_in"], p["w_out"], jnp.arange(p["w_in"].shape[0])))
    if shared:
        y = y + _relu2_mlp(x, p["shared"]["w_in"], p["shared"]["w_out"])
    return y.reshape(bsz, S, d)


def _attention(m, h, p, q_block: int = 512):
    bsz, S, _ = h.shape
    hq, hk, hd = m["hq"], m["hk"], m["hd"]
    q = (h @ p["wq"].astype(h.dtype)).reshape(bsz, S, hq, hd)
    k = jnp.repeat((h @ p["wk"].astype(h.dtype)).reshape(bsz, S, hk, hd),
                   hq // hk, axis=2)
    v = jnp.repeat((h @ p["wv"].astype(h.dtype)).reshape(bsz, S, hk, hd),
                   hq // hk, axis=2)
    qb = min(q_block, S)

    @jax.checkpoint
    def block(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k).astype(jnp.float32) * hd ** -0.5
        seen = (jnp.arange(S)[None, :] <= (i * qb + jnp.arange(qb))[:, None])
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return None, jnp.einsum("bhqk,bkhd->bqhd",
                                jax.nn.softmax(s, axis=-1).astype(v.dtype), v)

    _, out = jax.lax.scan(block, None, jnp.arange(S // qb))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, S, hq * hd)
    return out @ p["wo"].astype(out.dtype)


def block(cfg, m, kind: str, x, lp):
    h = _rmsnorm(x, lp["ln1"]["scale"], cfg["norm_eps"])
    if kind == "M":
        return x + _mamba(cfg, m, h, lp["ssm"])
    if kind == "E":
        return x + moe(cfg, m, h, lp["moe"])
    return x + _attention(m, h, lp["attn"])


def loss(params, tokens, labels, cfg: dict, ce_chunk: int = 256):
    """Mean next-token cross-entropy; blocks and logit chunks are
    rematerialized so the reference fits beside nothing else."""
    m = dims(cfg)
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    for (kind, _), run in zip(runs(cfg), params["blocks"]):
        def body(x, lp, kind=kind):
            return jax.checkpoint(lambda x, lp: block(cfg, m, kind, x, lp))(
                x, lp), None
        x, _ = jax.lax.scan(body, x, run)
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg["norm_eps"])
    bsz, S, d = x.shape
    cs = min(ce_chunk, S)
    hc = jnp.moveaxis(x.reshape(bsz, S // cs, cs, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(bsz, S // cs, cs), 1, 0)
    head = params["embed"]["unembed"]

    @jax.checkpoint
    def chunk_nll(h, lab):
        logits = (h @ head.astype(h.dtype)).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    def ce(tot, xs):
        return tot + chunk_nll(*xs), None

    total, _ = jax.lax.scan(ce, jnp.zeros((), jnp.float32), (hc, lc))
    return total / labels.size


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def expert_flops_per_assignment(cfg: dict) -> int:
    """One token's pass through one relu^2 expert, forward and backward:
    2 matmuls of d x ff forward (2 FLOP per multiply-add), twice that
    backward (the input's and the weights' gradients): 12 d ff."""
    m = dims(cfg)
    return 12 * m["d"] * m["ff"]


def expert_weight_bytes(cfg: dict) -> int:
    """Bytes of the held experts' float32 weights one E block moves in one
    training step, at the least: the weights read by the forward and again
    by the backward, and their gradient written."""
    m = dims(cfg)
    return 3 * 4 * m["E"] * 2 * m["d"] * m["ff"]


def expert_activation_bytes_per_assignment(cfg: dict) -> int:
    """Float32 rows one assignment moves through the held experts: its
    input read by the forward and by the weights' gradient, its output
    written, the output's gradient read and the input's gradient written."""
    return 5 * 4 * dims(cfg)["d"]


def forward_flops_per_token(cfg: dict, seq: int) -> int:
    """Multiply-adds x 2 of one token's forward pass through the chip's
    share at sequence length ``seq``: every projection; the SSD in its
    chunked form (C.B scores per group, the score-weighted sum of inputs,
    each chunk's own end state, and the entering state read out by C);
    the router, the shared expert and the held experts at the assignments
    they expect, ``k * E / n_routed_experts`` a token; causal attention
    (on average (seq + 1) / 2 keys a query, for scores and values); the
    head. The embedding gather, the convolution, the norms and the
    elementwise work are not counted."""
    m = dims(cfg)
    d, q = m["d"], min(cfg["chunk_size"], seq)
    h, p, n, g = m["heads"], m["p"], m["n"], m["g"]
    mamba = (d * m["proj"] + m["d_inner"] * d
             + q * g * n + q * h * p + 2 * h * p * n)
    held = m["k"] * m["E"] * 2 * d * m["ff"] // m["E_router"]
    moe = d * m["E_router"] + 2 * d * m["ff_shared"] + held
    hq, hk, hd = m["hq"], m["hk"], m["hd"]
    proj = d * (hq + 2 * hk) * hd + hq * hd * d
    macs = {"M": mamba, "E": moe, "*": proj}
    total = 2 * (sum(macs[c] for c in pattern(cfg)) + m["V"] * d)
    attn = pattern(cfg).count("*") * 2 * hq * hd * (seq + 1)
    return total + attn


def train_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward and backward: three times the forward; recomputation is not
    counted."""
    return 3 * forward_flops_per_token(cfg, seq)
