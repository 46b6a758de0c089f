"""Mixture-of-Experts: top-k router + capacity-bounded GATHER dispatch.

Dispatch avoids the O(T·E·C·d) one-hot einsum of GShard-style
implementations: token->slot assignment is computed with integer sorts and
scatters (O(T·k log + T·E) bookkeeping), tokens are *gathered* into a dense
[E, C, d] buffer, experts run as one batched matmul (MXU-friendly), and
results are gathered back per (token, k). Experts are sharded over the
``model`` mesh axis; GSPMD turns the data->expert redistribution into
all-to-all-style collectives (a hillclimb target — see EXPERIMENTS.md §Perf).

Covers DBRX (16e top-4) and DeepSeek-V2 (2 shared + 160 routed top-6).

``moe_dropless`` is the layer of Nemotron-H's expert blocks: a sigmoid
router with a selection-only bias, no capacity (every assignment to a held
expert is computed, through ``jax.lax.ragged_dot`` over the assignments
sorted by expert), and a layer that holds experts ``[offset, offset + E)``
of the router's width: one chip's share under expert parallelism. Tokens
routed to experts it does not hold add nothing here.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import apply_mlp, dense_init, init_mlp
from repro.sharding import shard


def moe_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    """Per-expert slot count, padded to a multiple of 8 for TPU tiling."""
    c = cfg.capacity_factor * num_tokens * cfg.num_experts_per_tok / cfg.num_experts
    return max(8, int(math.ceil(c / 8.0)) * 8)


def init_moe(key, cfg: ModelConfig, dtype) -> Dict:
    e, d = cfg.num_experts, cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    gated = cfg.mlp_variant in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], d, (d, cfg.num_router_experts), jnp.float32),
        "w_in": dense_init(ks[1], d, (e, d, ff), dtype),
        "w_out": dense_init(ks[2], ff, (e, ff, d), dtype),
    }
    if cfg.moe_router == "sigmoid":             # e_score_correction_bias
        p["router_bias"] = jnp.zeros((cfg.num_router_experts,), jnp.float32)
    if gated:
        p["w_gate"] = dense_init(ks[3], d, (e, d, ff), dtype)
    if cfg.num_shared_experts:
        shared_ff = cfg.shared_expert_d_ff or ff * cfg.num_shared_experts
        import dataclasses
        shared_cfg = dataclasses.replace(cfg, mlp_bias=False)
        p["shared"] = init_mlp(ks[4], shared_cfg, d, shared_ff, dtype)
    return p


def _expert_ffn(p: Dict, xe: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """xe: [E, C, d] -> [E, C, d], batched over experts."""
    h = jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    v = cfg.mlp_variant
    if v == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * h
    elif v == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]),
                        approximate=True) * h
    elif v == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"])


def route(router_w: jnp.ndarray, x_flat: jnp.ndarray, cfg: ModelConfig,
          bias=None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (weights [T,k], expert_idx [T,k] int32, aux_loss scalar)."""
    if cfg.moe_router == "sigmoid":
        return _route_sigmoid(router_w, x_flat, cfg, bias)
    logits = (x_flat.astype(jnp.float32) @ router_w)          # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weights = weights / jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = cfg.num_experts
    f = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1)) * cfg.num_experts_per_tok
    pbar = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pbar) * cfg.router_aux_loss_coef
    return weights.astype(x_flat.dtype), idx.astype(jnp.int32), aux


def _route_sigmoid(router_w, x_flat, cfg: ModelConfig, bias):
    """DeepSeek-V3 / Nemotron-H routing: float32 logits (at full precision:
    a single bfloat16 pass flips near-tied choices), sigmoid scores, the
    top k chosen on ``scores + bias`` with the bias out of the gradient,
    weighted by the plain scores, normalized to sum 1 when
    ``norm_topk_prob``, then times ``routed_scaling``. No auxiliary loss."""
    logits = jnp.dot(x_flat.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg.routed_scaling
    return weights, idx.astype(jnp.int32), jnp.zeros((), jnp.float32)


def moe_dropless(p: Dict, x: jnp.ndarray, cfg: ModelConfig, *,
                 offset: int = 0, shared: bool = True
                 ) -> Tuple[jnp.ndarray, Dict]:
    """x: [B,S,d] -> ([B,S,d], counters). The layer holds the experts of
    ``p["w_in"]``, experts ``[offset, offset + E)`` of the router's width;
    it routes over all of them and computes its own experts' part of the
    result, plus the shared expert when ``shared``. Dropless: the T*k
    assignments are sorted by expert (those to absent experts last) and
    the held groups run through ``ragged_dot``; rows past the held groups
    are masked out, so they add nothing in either direction.

    counters: ``moe_assignments`` (assignments the held experts compute)
    and ``moe_dropped`` (assignments routed to a held expert that the
    ragged dots do not compute for it: 0, see ``computed_rows``)."""
    if cfg.mlp_variant != "squared_relu":
        raise NotImplementedError(
            f"moe_dropless computes relu^2 experts, not {cfg.mlp_variant!r}")
    B, S, d = x.shape
    T, k = B * S, cfg.num_experts_per_tok
    E = p["w_in"].shape[0]
    x_flat = x.reshape(T, d)
    weights, idx, _ = route(p["router"], x_flat, cfg, p.get("router_bias"))
    local = idx.reshape(-1) - offset                          # [T*k]
    held = (local >= 0) & (local < E)
    group = jnp.where(held, local, E)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((E + 1,), jnp.int32).at[group].add(1)[:E]
    rows = jnp.arange(T * k) < jnp.sum(sizes)                 # held, sorted
    xs = jnp.where(rows[:, None], x_flat[order // k], 0)    # token of each
    with jax.named_scope("moe_experts"):
        h = jnp.where(rows[:, None], jax.lax.ragged_dot(xs, p["w_in"], sizes), 0)
        h = jnp.square(jax.nn.relu(h))
        ys = jnp.where(rows[:, None], jax.lax.ragged_dot(h, p["w_out"], sizes), 0)
    # back to (token, k) order by the inverse permutation, then weighted
    unsort = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    w = jnp.where(held, weights.reshape(-1), 0).astype(ys.dtype)
    y = jnp.sum((ys[unsort] * w[:, None]).reshape(T, k, d), axis=1)
    if shared and "shared" in p:
        y = y + apply_mlp(p["shared"], x_flat, cfg)
    done = computed_rows(group[order], sizes)
    counters = {"moe_assignments": done,
                "moe_dropped": jnp.sum(held.astype(jnp.int32)) - done}
    return y.reshape(B, S, d), counters


def computed_rows(sorted_group: jnp.ndarray, sizes: jnp.ndarray
                  ) -> jnp.ndarray:
    """How many of the sorted assignment rows the ragged dots compute for
    the expert they were routed to. ``ragged_dot`` gives row r to the group
    whose span of the cumulative ``sizes`` holds r; a row counts when that
    group is its own held expert. A capacity cap on ``sizes``, or rows out
    of expert order, leaves held rows uncounted."""
    E = sizes.shape[0]
    at = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(sorted_group.shape[0]),
                          side="right", method="compare_all")
    return jnp.sum(((at == sorted_group) & (sorted_group < E)).astype(jnp.int32))


def dispatch_indices(idx: jnp.ndarray, num_experts: int, capacity: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Integer-only slotting. idx: [T, k] expert ids.

    Returns:
      token_for_slot [E*C] int32 (-1 = empty slot)
      slot_for_assign [T, k] int32 (-1 = dropped)
      keep [T, k] bool
    """
    T, k = idx.shape
    flat = idx.reshape(-1)                                    # [T*k]
    # position of each assignment within its expert, in token order
    onehot = jax.nn.one_hot(flat, num_experts, dtype=jnp.int32)   # [T*k, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot                # exclusive
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                     # [T*k]
    keep = pos < capacity
    slot = jnp.where(keep, flat * capacity + pos, -1).astype(jnp.int32)
    token_id = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    token_for_slot = jnp.full((num_experts * capacity,), -1, jnp.int32)
    token_for_slot = token_for_slot.at[jnp.where(keep, slot, num_experts * capacity)
                                       ].set(token_id, mode="drop")
    return token_for_slot, slot.reshape(T, k), keep.reshape(T, k)


def moe_ffn(p: Dict, x: jnp.ndarray, cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B,S,d] -> ([B,S,d], aux_loss). Dispatches to the expert-parallel
    shard_map path when a production mesh is installed and the token count
    supports it; otherwise the single-program gather path below."""
    from repro.sharding.context import current_mesh_info
    info = current_mesh_info()
    if info is not None and cfg.num_experts % info.tp_size == 0:
        B, S, _ = x.shape
        t_loc = (B // max(_batch_shards(info, B), 1)) * S
        if t_loc % info.tp_size == 0 and t_loc // info.tp_size >= 8:
            return moe_ffn_ep(p, x, cfg, info)
    return _moe_ffn_gather(p, x, cfg)


def _batch_shards(info, batch: int) -> int:
    if batch % info.dp_size == 0:
        return info.dp_size
    last = int(info.mesh.shape[info.dp_axes[-1]])
    return last if batch % last == 0 else 1


def _moe_ffn_gather(p: Dict, x: jnp.ndarray, cfg: ModelConfig
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, d = x.shape
    T = B * S
    x_flat = x.reshape(T, d)
    weights, idx, aux = route(p["router"], x_flat, cfg)
    C = moe_capacity(cfg, T)
    token_for_slot, slot_for_assign, keep = dispatch_indices(idx, cfg.num_experts, C)

    # ---- gather tokens into expert buffers ----
    safe_tok = jnp.maximum(token_for_slot, 0)
    xe = x_flat[safe_tok] * (token_for_slot >= 0)[:, None].astype(x.dtype)
    xe = xe.reshape(cfg.num_experts, C, d)
    xe = shard(xe, "moe_ecd")
    ye = _expert_ffn(p, xe, cfg)
    ye = shard(ye, "moe_ecd")
    ye_flat = ye.reshape(cfg.num_experts * C, d)

    # ---- combine back per assignment ----
    safe_slot = jnp.maximum(slot_for_assign, 0)               # [T,k]
    per_assign = ye_flat[safe_slot.reshape(-1)].reshape(T, cfg.num_experts_per_tok, d)
    w = (weights * keep.astype(weights.dtype))[..., None]
    y = jnp.sum(per_assign * w, axis=1)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], x_flat, cfg)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (production mesh)
# ---------------------------------------------------------------------------
#
# tokens are split across the `model` axis inside each data shard, routed
# locally, dispatched to per-expert buffers, ALL-TO-ALL'd so each device
# holds the slots of its E/tp experts, batch-matmul'd, all-to-all'd back and
# combined; the token slices are reassembled with an all-gather. Expert
# weights enter the region with in_spec P(model, ...) — GSPMD inserts the
# ZeRO-3 un-shard over `data` at the boundary. This is the paper-relevant
# collective pattern (§2.4 Allreduce / pairwise communication) applied to
# expert parallelism.

def moe_ffn_ep(p: Dict, x: jnp.ndarray, cfg: ModelConfig, info
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    tp = info.tp_axis
    tpn = info.tp_size
    bsh = _batch_shards(info, B)
    dp_used = info.dp_axes if bsh == info.dp_size else info.dp_axes[-1:]
    bspec = dp_used if len(dp_used) > 1 else (dp_used[0] if bsh > 1 else None)
    t_loc = (B // bsh) * S
    sl = t_loc // tpn                      # tokens routed per device
    C_sub = moe_capacity(cfg, sl)
    gated = "w_gate" in p

    def local_fn(router, w_in, w_gate, w_out, shared, x_blk):
        tid = jax.lax.axis_index(tp)
        xs = x_blk.reshape(t_loc, d)
        my = jax.lax.dynamic_slice(xs, (tid * sl, 0), (sl, d))
        weights, idx, aux = route(router, my, cfg)
        token_for_slot, slot_for_assign, keep = dispatch_indices(
            idx, cfg.num_experts, C_sub)
        safe_tok = jnp.maximum(token_for_slot, 0)
        xe = my[safe_tok] * (token_for_slot >= 0)[:, None].astype(my.dtype)
        xe = xe.reshape(cfg.num_experts, C_sub, d)
        # -> [e_loc, tpn*C_sub, d]: each device receives its experts' slots
        xe = jax.lax.all_to_all(xe, tp, split_axis=0, concat_axis=1, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", xe, w_in)
        if gated:
            g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
            if cfg.mlp_variant == "geglu":
                h = jax.nn.gelu(g, approximate=True) * h
            else:
                h = jax.nn.silu(g) * h
        elif cfg.mlp_variant == "squared_relu":
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.gelu(h, approximate=True)
        ye = jnp.einsum("ecf,efd->ecd", h, w_out)
        ye = jax.lax.all_to_all(ye, tp, split_axis=1, concat_axis=0, tiled=True)
        ye_flat = ye.reshape(cfg.num_experts * C_sub, d)
        safe_slot = jnp.maximum(slot_for_assign, 0)
        per_assign = ye_flat[safe_slot.reshape(-1)].reshape(
            sl, cfg.num_experts_per_tok, d)
        w = (weights * keep.astype(weights.dtype))[..., None]
        y_my = jnp.sum(per_assign * w, axis=1)
        if shared is not None:
            y_my = y_my + apply_mlp(shared, my, cfg)
        y = jax.lax.all_gather(y_my, tp, axis=0, tiled=True)   # [t_loc, d]
        aux = jax.lax.pmean(aux, tp)
        for ax in dp_used:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(x_blk.shape), aux

    shared = p.get("shared")
    shared_spec = (jax.tree.map(lambda _: P(), shared)
                   if shared is not None else None)
    fn = jax.shard_map(
        local_fn, mesh=info.mesh,
        in_specs=(P(), P(tp, None, None),
                  P(tp, None, None) if gated else P(),
                  P(tp, None, None), shared_spec, P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    y, aux = fn(p["router"], p["w_in"], p.get("w_gate"), p["w_out"],
                shared, x)
    return y, aux
