"""Public model API: ``build_model(cfg)`` returns a ``Model`` facade with
pure functions for init / train loss / prefill / decode, uniform across all
ten architectures. Batch schemas:

  LM families (dense/moe/ssm/hybrid/vlm):
      train:   {"tokens": [B,S] i32, "labels": [B,S] i32}
      prefill: {"tokens": [B,S]}
      decode:  {"token":  [B,1]}
  audio (musicgen — frontend stub provides embeddings):
      train:   {"embeds": [B,S,d], "cross_context": [B,Tc,cd], "labels": [B,S,K] i32}
      prefill: {"embeds": ..., "cross_context": ...}
      decode:  {"embed": [B,1,d]}
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict

import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import transformer
from repro.models.layers import chunked_cross_entropy, cross_entropy


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    prefill: Callable
    decode: Callable
    make_cache: Callable
    #: False where the loss holds an operation that takes no batch dimension
    #: on the TPU (XLA's ragged dot, in the dropless MoE): engines then
    #: train clients one after another, each device its own, not vmapped
    client_vmap: bool = True


def _forward_kwargs(cfg: ModelConfig, batch: Dict) -> Dict:
    kw: Dict = {}
    if cfg.family == "audio":
        kw["embeds"] = batch.get("embeds", batch.get("embed"))
        if "cross_context" in batch:
            kw["cross_context"] = batch["cross_context"]
    else:
        kw["tokens"] = batch.get("tokens", batch.get("token"))
    return kw


def build_model(cfg: ModelConfig) -> Model:

    def init(key, dtype=jnp.float32):
        return transformer.init_params(key, cfg, dtype)

    def loss_fn(params, batch, *, remat: bool = False):
        """-> (loss, {"ce", "aux"[, "counters"]}); a layer-pattern model's
        aux carries its blocks' counters (summed over the blocks)."""
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if mask is None:
            # fused chunked unembed+CE: full [B,S,V] f32 logits never exist
            h, _, aux, counters = transformer.forward(
                params, cfg, cache=None, remat=remat, return_hidden=True,
                with_counters=True, **_forward_kwargs(cfg, batch))
            heads = params.get("heads") if cfg.family == "audio" else None
            embed = params.get("embed")
            ce = chunked_cross_entropy(embed, h, labels, cfg, heads=heads)
        else:
            logits, _, aux, counters = transformer.forward(
                params, cfg, cache=None, remat=remat, with_counters=True,
                **_forward_kwargs(cfg, batch))
            ce = cross_entropy(logits, labels, mask)
        loss = ce + aux
        metrics = {"ce": ce, "aux": aux}
        if counters:
            metrics["counters"] = counters
        return loss, metrics

    def make_cache(batch: int, buf_len: int, dtype=jnp.float32,
                   cross_len: int = 0):
        return transformer.init_cache(cfg, batch, buf_len, dtype,
                                      cross_len=cross_len)

    def prefill(params, batch, cache):
        logits, cache, _ = transformer.forward(
            params, cfg, cache=cache, **_forward_kwargs(cfg, batch))
        return logits[:, -1:], cache

    def decode(params, cache, batch):
        logits, cache, _ = transformer.forward(
            params, cfg, cache=cache, **_forward_kwargs(cfg, batch))
        return logits[:, -1], cache

    return Model(cfg=cfg, init=init, loss_fn=loss_fn, prefill=prefill,
                 decode=decode, make_cache=make_cache,
                 client_vmap="E" not in cfg.pattern)
