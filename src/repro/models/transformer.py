"""Unified decoder backbone covering all six assigned architecture families.

One scan-over-layers program (stacked [L, ...] params) so 60-layer models
lower to compact HLO. Per-family block composition:

  dense / vlm : attn -> mlp
  audio       : attn -> cross-attn -> mlp            (musicgen conditioning)
  moe         : attn|mla -> moe (+ optional leading dense layers, deepseek)
  ssm         : ssd mixer only                        (mamba2)
  hybrid      : (attn ∥ ssm, mean-combined) -> mlp    (hymba, + meta tokens)

A ``layer_pattern`` (Nemotron-H) replaces the homogeneous stack: each block
is ``x + mixer(RMSNorm(x))`` with one mixer, "M" (Mamba-2), "E" (dropless
MoE) or "*" (attention), in the published order. Its parameters are
stacked per run of equal blocks (``params["blocks"]``, one dict per run,
leading dim the run's length), and each run is one scan: the gradient of a
run's stack is formed whole, never padded into a larger stack.

Caches for serving are stacked [L, ...] and scanned alongside params; ring
/pinned-slot addressing is computed once per step at the top level.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import cache_write_slot
from repro.models.layers import (
    apply_mlp, apply_norm, compute_logits, dense_init, embed_init,
    embed_tokens, init_embed, init_mlp, init_norm,
)
from repro.sharding import shard


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, dtype, *, moe_layer: bool):
    ks = jax.random.split(key, 8)
    p: Dict = {"ln1": init_norm(cfg, cfg.d_model, dtype)}
    if cfg.family == "ssm":
        p["ssm"] = ssm_mod.init_ssm(ks[0], ssm_mod.ssm_dims(cfg), dtype)
        return p
    if cfg.use_mla:
        p["attn"] = mla_mod.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.init_ssm(ks[1], ssm_mod.ssm_dims(cfg), dtype)
        p["attn_branch_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["ssm_branch_norm"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.cross_attend:
        p["ln_cross"] = init_norm(cfg, cfg.d_model, dtype)
        p["cross"] = attn_mod.init_cross_attention(ks[2], cfg, dtype)
    p["ln2"] = init_norm(cfg, cfg.d_model, dtype)
    if moe_layer:
        p["moe"] = moe_mod.init_moe(ks[3], cfg, dtype)
    else:
        ff = cfg.moe_dense_d_ff if (cfg.family == "moe" and cfg.moe_dense_d_ff) else cfg.d_ff
        p["mlp"] = init_mlp(ks[3], cfg, cfg.d_model, ff, dtype)
    return p


#: the parameter key of each pattern block's mixer
MIXERS = {"M": "ssm", "E": "moe", "*": "attn"}


def pattern_runs(pattern: str) -> list:
    """[(kind, length)] of the runs of equal blocks, in order."""
    runs = []
    for c in pattern:
        if runs and runs[-1][0] == c:
            runs[-1] = (c, runs[-1][1] + 1)
        else:
            runs.append((c, 1))
    return runs


def _init_block(key, cfg: ModelConfig, kind: str, dtype) -> Dict:
    init = {"M": lambda k: ssm_mod.init_ssm(k, ssm_mod.ssm_dims(cfg), dtype),
            "E": lambda k: moe_mod.init_moe(k, cfg, dtype),
            "*": lambda k: attn_mod.init_attention(k, cfg, dtype)}[kind]
    return {"ln1": init_norm(cfg, cfg.d_model, dtype), MIXERS[kind]: init(key)}


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    if cfg.layer_pattern:
        k_embed, k_blocks = jax.random.split(key)
        runs = pattern_runs(cfg.pattern)
        run_keys = jax.random.split(k_blocks, len(runs))
        return {
            "embed": init_embed(k_embed, cfg, dtype),
            "blocks": [jax.vmap(lambda k, c=c: _init_block(k, cfg, c, dtype))(
                jax.random.split(rk, n)) for (c, n), rk in zip(runs, run_keys)],
            "ln_f": init_norm(cfg, cfg.d_model, dtype),
        }
    keys = jax.random.split(key, 4)
    params: Dict = {}
    if cfg.family != "audio":
        params["embed"] = init_embed(keys[0], cfg, dtype)
    else:
        params["heads"] = dense_init(keys[0], cfg.d_model,
                                     (cfg.d_model, cfg.num_codebooks * cfg.vocab_size),
                                     dtype)
    if cfg.num_meta_tokens:
        params["meta"] = embed_init(keys[3], (cfg.num_meta_tokens, cfg.d_model), dtype)

    fd = cfg.first_dense_layers
    n_scan = cfg.num_layers - fd
    layer_keys = jax.random.split(keys[1], n_scan)
    moe_layer = cfg.family == "moe"
    params["layers"] = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype, moe_layer=moe_layer))(layer_keys)
    if fd:
        dkeys = jax.random.split(keys[2], fd)
        params["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, dtype, moe_layer=False))(dkeys)
    params["ln_f"] = init_norm(cfg, cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# Caches (stacked over layers)
# ---------------------------------------------------------------------------

def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """[L_scan] per-layer window (0 = full attention)."""
    fd = cfg.first_dense_layers
    idx = jnp.arange(cfg.num_layers - fd) + fd
    if cfg.sliding_window and cfg.global_layer_every:
        return jnp.where(idx % cfg.global_layer_every == 0, 0,
                         cfg.sliding_window).astype(jnp.int32)
    return jnp.full_like(idx, cfg.sliding_window, dtype=jnp.int32)


def init_cache(cfg: ModelConfig, batch: int, buf_len: int, dtype,
               cross_len: int = 0) -> Dict:
    """buf_len: KV buffer slots (callers choose full length or window+meta)."""
    L = cfg.num_layers
    cache: Dict = {
        "index": jnp.zeros((), jnp.int32),
        "slot_pos": jnp.full((buf_len,), -1, jnp.int32),
    }
    if cfg.layer_pattern:
        cache["blocks"] = [_block_cache(cfg, c, n, batch, buf_len, dtype)
                           for c, n in pattern_runs(cfg.pattern)]
        return cache
    if cfg.family == "ssm" or cfg.family == "hybrid":
        dims = ssm_mod.ssm_dims(cfg)
        cache["conv"] = jnp.zeros((L, batch, dims.conv_width - 1, dims.conv_ch), dtype)
        cache["state"] = jnp.zeros((L, batch, dims.nheads, dims.headdim,
                                    dims.nstate), jnp.float32)
    if cfg.family != "ssm":
        if cfg.use_mla:
            cache["latent"] = jnp.zeros((L, batch, buf_len, cfg.kv_lora_rank), dtype)
            cache["k_rope"] = jnp.zeros((L, batch, buf_len, cfg.qk_rope_head_dim), dtype)
        else:
            hk, hd = cfg.num_kv_heads, cfg.head_dim
            cache["k"] = jnp.zeros((L, batch, buf_len, hk, hd), dtype)
            cache["v"] = jnp.zeros((L, batch, buf_len, hk, hd), dtype)
    if cfg.cross_attend:
        hq, hd = cfg.num_heads, cfg.head_dim
        cache["cross_k"] = jnp.zeros((L, batch, cross_len, hq, hd), dtype)
        cache["cross_v"] = jnp.zeros((L, batch, cross_len, hq, hd), dtype)
    return cache


def _block_cache(cfg: ModelConfig, kind: str, n: int, batch: int,
                 buf_len: int, dtype) -> Dict:
    if kind == "M":
        dims = ssm_mod.ssm_dims(cfg)
        return {"conv": jnp.zeros((n, batch, dims.conv_width - 1, dims.conv_ch),
                                  dtype),
                "state": jnp.zeros((n, batch, dims.nheads, dims.headdim,
                                    dims.nstate), jnp.float32)}
    if kind == "*":
        shape = (n, batch, buf_len, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return {}


_PER_LAYER_KEYS = ("k", "v", "latent", "k_rope", "conv", "state",
                   "cross_k", "cross_v")


def _split_cache(cache: Optional[Dict], fd: int):
    """-> (dense-layer bufs, scanned-layer bufs) with leading L dims."""
    if cache is None:
        return {}, {}
    per_layer = {k: v for k, v in cache.items() if k in _PER_LAYER_KEYS}
    head = {k: v[:fd] for k, v in per_layer.items()} if fd else {}
    tail = {k: v[fd:] for k, v in per_layer.items()}
    return head, tail


# ---------------------------------------------------------------------------
# One layer
# ---------------------------------------------------------------------------

def _layer_forward(lp: Dict, x, bufs: Dict, cfg: ModelConfig, *,
                   positions, window, kv_pos, write_slot, cross_context,
                   moe_layer: bool):
    """Returns (x_out, new_bufs, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_bufs: Dict = {}
    num_meta = cfg.num_meta_tokens
    h = apply_norm(lp["ln1"], x, cfg)
    h = shard(h, "act_btd")

    if cfg.family == "ssm":
        ssm_cache = ({"conv": bufs["conv"], "state": bufs["state"]}
                     if "conv" in bufs else None)
        y, new_ssm = ssm_mod.ssm_mixer(lp["ssm"], h, ssm_mod.ssm_dims(cfg),
                                       cache=ssm_cache)
        if new_ssm is not None:
            new_bufs.update(new_ssm)
        return x + y, new_bufs, aux

    kv_bufs = None
    if "k" in bufs:
        kv_bufs = (bufs["k"], bufs["v"])
    elif "latent" in bufs:
        kv_bufs = (bufs["latent"], bufs["k_rope"])
    attn_fn = mla_mod.mla_attention if cfg.use_mla else attn_mod.attention
    y_attn, new_kv = attn_fn(lp["attn"], h, cfg, positions=positions,
                             window=window, num_meta=num_meta,
                             kv_bufs=kv_bufs, kv_pos=kv_pos,
                             write_slot=write_slot)
    if new_kv is not None:
        if cfg.use_mla:
            new_bufs["latent"], new_bufs["k_rope"] = new_kv
        else:
            new_bufs["k"], new_bufs["v"] = new_kv

    if cfg.family == "hybrid":
        ssm_cache = ({"conv": bufs["conv"], "state": bufs["state"]}
                     if "conv" in bufs else None)
        y_ssm, new_ssm = ssm_mod.ssm_mixer(lp["ssm"], h, ssm_mod.ssm_dims(cfg),
                                           cache=ssm_cache)
        if new_ssm is not None:
            new_bufs.update(new_ssm)
        from repro.models.layers import rms_normalize
        y = 0.5 * (rms_normalize(y_attn, lp["attn_branch_norm"]) +
                   rms_normalize(y_ssm, lp["ssm_branch_norm"]))
    else:
        y = y_attn
    x = x + y

    if cfg.cross_attend:
        hc = apply_norm(lp["ln_cross"], x, cfg)
        cross_kv = ((bufs["cross_k"], bufs["cross_v"])
                    if ("cross_k" in bufs and cross_context is None) else None)
        y_cross, (ck, cv) = attn_mod.cross_attention(
            lp["cross"], hc, cfg, context=cross_context, cross_kv=cross_kv)
        x = x + y_cross
        if "cross_k" in bufs:
            new_bufs["cross_k"], new_bufs["cross_v"] = ck, cv

    h2 = apply_norm(lp["ln2"], x, cfg)
    h2 = shard(h2, "act_btd")
    if moe_layer:
        y2, aux = moe_mod.moe_ffn(lp["moe"], h2, cfg)
    else:
        y2 = apply_mlp(lp["mlp"], h2, cfg)
    return x + y2, new_bufs, aux


def _block_forward(kind: str, lp: Dict, x, bufs: Dict, cfg: ModelConfig, *,
                   positions, kv_pos, write_slot):
    """One pattern block, ``x + mixer(RMSNorm(x))``. Returns (x_out,
    new_bufs, counters)."""
    h = apply_norm(lp["ln1"], x, cfg)
    if kind == "M":
        with jax.named_scope("ssm"):
            y, new = ssm_mod.ssm_mixer(lp["ssm"], h, ssm_mod.ssm_dims(cfg),
                                       cache=bufs or None)
        return x + y, new or {}, {}
    if kind == "E":
        with jax.named_scope("moe"):
            y, counters = moe_mod.moe_dropless(lp["moe"], h, cfg)
        return x + y, {}, counters
    with jax.named_scope("attention"):
        y, new_kv = attn_mod.attention(
            lp["attn"], h, cfg, positions=positions,
            kv_bufs=(bufs["k"], bufs["v"]) if bufs else None,
            kv_pos=kv_pos, write_slot=write_slot)
    return x + y, ({"k": new_kv[0], "v": new_kv[1]} if new_kv else {}), {}


def _pattern_stack(blocks: list, x, block_bufs, cfg: ModelConfig, *, remat,
                   positions, kv_pos, write_slot):
    """Every run of equal blocks as one scan. Returns (x, [new bufs per
    run], counters summed over the blocks)."""
    totals: Dict = {}
    new_bufs = []
    for (kind, _), lp_run, bufs in zip(pattern_runs(cfg.pattern), blocks,
                                       block_bufs):
        def step(xc, xs, kind=kind):
            lp, b = xs
            out, nb, counts = _block_forward(
                kind, lp, xc, b, cfg, positions=positions, kv_pos=kv_pos,
                write_slot=write_slot)
            return out, (nb, counts)

        x, (nb, counts) = jax.lax.scan(
            jax.checkpoint(step) if remat else step, x, (lp_run, bufs))
        new_bufs.append(nb)
        for name, v in counts.items():
            totals[name] = totals.get(name, 0) + jnp.sum(v)
    return x, new_bufs, totals


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------

def forward(params: Dict, cfg: ModelConfig, *,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            cross_context: Optional[jnp.ndarray] = None,
            cache: Optional[Dict] = None,
            remat: bool = False,
            return_hidden: bool = False,
            with_counters: bool = False,
            ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (logits, new_cache, aux_loss) — or (hidden, ...) when
    ``return_hidden`` (training uses the fused chunked unembed+CE instead);
    ``with_counters`` appends the blocks' counters (MoE assignments).

    Train: cache None. Prefill: fresh cache, S>1. Decode: cache, S==1.
    logits: [B,S,V] ([B,S,K,V] for audio); meta-token positions stripped.
    """
    if embeds is None:
        x = embed_tokens(params["embed"], tokens, cfg)
    else:
        x = embeds
    B, S_in, _ = x.shape
    M = cfg.num_meta_tokens
    decode = cache is not None and S_in == 1   # one-token step with history

    if M and not decode:
        meta = jnp.broadcast_to(params["meta"][None], (B, M, cfg.d_model)).astype(x.dtype)
        x = jnp.concatenate([meta, x], axis=1)
    S = x.shape[1]
    x = shard(x, "act_btd")

    # ---- positions / cache slots ----
    write_slot = None
    kv_pos = None
    new_cache = None
    if cache is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    elif decode:
        idx = cache["index"]
        positions = jnp.full((B, 1), idx, jnp.int32)
        buf = cache["slot_pos"].shape[0]
        write_slot = cache_write_slot(buf, idx, M)
        kv_pos = cache["slot_pos"].at[write_slot].set(idx)
    else:                                            # prefill
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        buf = cache["slot_pos"].shape[0]
        kv_pos = jnp.where(jnp.arange(buf) < S, jnp.arange(buf), -1).astype(jnp.int32)

    aux_total = jnp.zeros((), jnp.float32)
    counters: Dict = {}
    if cfg.layer_pattern:
        block_bufs = (cache["blocks"] if cache is not None
                      else [{} for _ in pattern_runs(cfg.pattern)])
        x, new_blocks, counters = _pattern_stack(
            params["blocks"], x, block_bufs, cfg, remat=remat,
            positions=positions, kv_pos=kv_pos, write_slot=write_slot)
        if cache is not None:
            new_cache = dict(cache, blocks=new_blocks, slot_pos=kv_pos,
                             index=(cache["index"] + 1 if decode
                                    else jnp.asarray(S, jnp.int32)))
        x = apply_norm(params["ln_f"], x, cfg)
        out = x if return_hidden else shard(
            compute_logits(params["embed"], x, cfg), "act_btv")
        return (out, new_cache, aux_total) + ((counters,) if with_counters
                                               else ())

    fd = cfg.first_dense_layers
    head_bufs, tail_bufs = _split_cache(cache, fd)
    wins = layer_windows(cfg)

    def make_body(moe_layer: bool):
        def body(carry, xs):
            xc, aux_acc = carry
            lp, bufs, win = xs
            x_out, new_bufs, aux = _layer_forward(
                lp, xc, bufs, cfg, positions=positions, window=win,
                kv_pos=kv_pos, write_slot=write_slot,
                cross_context=cross_context, moe_layer=moe_layer)
            return (x_out, aux_acc + aux), new_bufs
        return jax.checkpoint(body) if remat else body

    new_per_layer = {}
    if fd:
        dwins = jnp.zeros((fd,), jnp.int32)
        (x, aux_total), new_head = jax.lax.scan(
            make_body(False),
            (x, aux_total), (params["dense_layers"], head_bufs, dwins))
    else:
        new_head = {}
    (x, aux_total), new_tail = jax.lax.scan(
        make_body(cfg.family == "moe"),
        (x, aux_total), (params["layers"], tail_bufs, wins))

    if cache is not None:
        new_per_layer = dict(new_tail)
        if fd:
            new_per_layer = {k: jnp.concatenate([new_head[k], new_tail[k]], axis=0)
                             for k in new_tail}
        new_cache = dict(cache)
        new_cache.update(new_per_layer)
        if decode:
            new_cache["slot_pos"] = kv_pos
            new_cache["index"] = cache["index"] + 1
        else:
            new_cache["slot_pos"] = kv_pos
            new_cache["index"] = jnp.asarray(S, jnp.int32)

    if M and not decode:
        x = x[:, M:]
    x = apply_norm(params["ln_f"], x, cfg)
    if return_hidden:
        return (x, new_cache, aux_total) + ((counters,) if with_counters
                                             else ())

    if cfg.family == "audio":
        logits = x @ params["heads"]
        logits = logits.reshape(B, x.shape[1], cfg.num_codebooks, cfg.vocab_size)
    else:
        logits = compute_logits(params["embed"], x, cfg)
    logits = shard(logits, "act_btv")
    return (logits, new_cache, aux_total) + ((counters,) if with_counters
                                             else ())
