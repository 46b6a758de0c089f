"""The one traffic generator: every mix under ``bench/traffic/`` is a JSON
file of parameters that names one of the kinds below.

``image_clients`` draws a federated image population with the statistics of
``repro.data.federated.pseudo_femnist_federated`` followed by
``pack_clients`` (low-frequency class templates plus Gaussian noise, client
sizes uniform in [per_client/2, per_client], a few label classes per
client, an 80/20 train/test split, zero padding with a 0/1 mask). Client
sizes and classes come from the seed on the host; the pixels are drawn on
the device in one jitted call. Every seed pads to the same shapes, so every
seed gives the program the same work.

``token_rounds`` draws per-client token batches with the statistics of
``repro.data.lm.token_stream_batches`` (a Zipf base vocabulary with a
deterministic successor taken with probability ``structure``), as a pool of
rounds laid out [rounds, clients, local_steps, batch, seq].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import STREAM_DATA, STREAM_TOKENS, numpy_rng, raw_key


def _make_templates(rng, num_classes: int, dim: int) -> np.ndarray:
    side = int(np.sqrt(dim))
    t = rng.normal(0, 1, (num_classes, side // 4 + 1, side // 4 + 1))
    up = np.kron(t, np.ones((4, 4)))[:, :side, :side]
    return up.reshape(num_classes, side * side).astype(np.float32)


def split_sizes(spec: dict):
    """(largest train count, largest test count) any client of this mix can
    have: every seed pads to these."""
    per, frac = int(spec["per_client"]), float(spec["test_frac"])
    n = np.arange(per // 2, per + 1)
    n_te = np.maximum(1, (n * frac).astype(int))
    return int((n - n_te).max()), int(n_te.max())


@functools.partial(jax.jit,
                   static_argnames=("n_max", "image_shape", "k"))
def _draw(key, templates, cls, n_real, noise, *, n_max, image_shape, k):
    N = cls.shape[0]
    k1, k2 = jax.random.split(key)
    pick = jax.random.randint(k1, (N, n_max), 0, k)
    y = jnp.take_along_axis(cls, pick, axis=1)
    x = templates[y] + noise * jax.random.normal(
        k2, (N, n_max, templates.shape[-1]), jnp.float32)
    mask = jnp.arange(n_max)[None, :] < n_real[:, None]
    x = jnp.where(mask[..., None], x, 0.0).reshape((N, n_max) + image_shape)
    y = jnp.where(mask, y, 0).astype(jnp.int32)
    return x, y, mask.astype(jnp.float32)


def image_clients(spec: dict, seed: int, image_size: int, channels: int,
                  with_test: bool = True) -> dict:
    """Device arrays ``x [N, n_tr, H, W, C]``, ``y``, ``mask``, ``counts``
    (float32 real-sample counts) and, with ``with_test``, ``test_x``,
    ``test_y``, ``test_mask``; plus host ``counts_np``/``test_counts_np``."""
    rng = numpy_rng(seed, STREAM_DATA)
    N, C = int(spec["data_clients"]), int(spec["label_classes"])
    k, per = int(spec["classes_per_client"]), int(spec["per_client"])
    dim = image_size * image_size * channels
    templates = _make_templates(rng, C, dim)
    sizes = rng.integers(per // 2, per + 1, size=N)
    n_te = np.maximum(1, (sizes * float(spec["test_frac"])).astype(int))
    n_tr = sizes - n_te
    cls = np.argsort(rng.random((N, C)), axis=1)[:, :k].astype(np.int32)
    n_max_tr, n_max_te = split_sizes(spec)
    k_tr, k_te = jax.random.split(raw_key(seed, STREAM_DATA))
    shape = (image_size, image_size, channels)
    tmpl, cls_d = jnp.asarray(templates), jnp.asarray(cls)
    noise = jnp.float32(spec["noise"])
    x, y, m = _draw(k_tr, tmpl, cls_d, jnp.asarray(n_tr), noise,
                    n_max=n_max_tr, image_shape=shape, k=k)
    out = {"x": x, "y": y, "mask": m,
           "counts": jnp.asarray(n_tr, jnp.float32),
           "counts_np": n_tr.astype(np.int64)}
    if with_test:
        tx, ty, tm = _draw(k_te, tmpl, cls_d, jnp.asarray(n_te), noise,
                           n_max=n_max_te, image_shape=shape, k=k)
        out.update(test_x=tx, test_y=ty, test_mask=tm,
                   test_counts_np=n_te.astype(np.int64))
    return out


def token_rounds(spec: dict, seed: int, *, clients: int, local_steps: int,
                 vocab_size: int) -> dict:
    """Host int32 ``tokens``/``labels`` [pool_rounds, clients, local_steps,
    batch, seq], one independent stream per client."""
    R, B, S = int(spec["pool_rounds"]), int(spec["batch"]), int(spec["seq"])
    structure = float(spec["structure"])
    base = min(vocab_size, int(spec["base_vocab"]))
    toks = np.empty((R, clients, local_steps, B, S + 1), np.int32)
    for c in range(clients):
        rng = numpy_rng(seed, STREAM_TOKENS * 1000 + c)
        successor = rng.integers(0, base, size=base)
        zipf = 1.0 / np.arange(1, base + 1) ** float(spec["zipf"])
        zipf /= zipf.sum()
        n = R * local_steps * B
        t = np.empty((n, S + 1), np.int32)
        t[:, 0] = rng.choice(base, size=n, p=zipf)
        det = rng.random((n, S)) < structure
        rnd = rng.choice(base, size=(n, S), p=zipf)
        for s in range(S):
            t[:, s + 1] = np.where(det[:, s], successor[t[:, s]], rnd[:, s])
        toks[:, c] = t.reshape(R, local_steps, B, S + 1)
    return {"tokens": np.ascontiguousarray(toks[..., :-1]),
            "labels": np.ascontiguousarray(toks[..., 1:])}
