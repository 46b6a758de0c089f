"""Plain FedP2P rounds (arXiv:2106.06627, Algorithm 2), independent of the
program: client-local SGD, then a data-weighted average inside each of L
clusters, then the unweighted mean of the cluster models (the global sync
every round). Randomness follows the program's documented key schedule, so
the reference sees the same clients, clusters and batch orders as the
timed path; the weights and the data come from the benchmark.

``dtype`` sets the precision of the whole reference (float32 at the
``highest`` matmul precision for the comparison; bfloat16 for the control).
``fault`` plants one of the faults the check must catch: ``half_batch``
(each local step sees only the first half of its batch) or ``no_exchange``
(clients keep their own models: the mixing is left out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = (None, "half_batch", "no_exchange")
#: the matmul precision of the float32 comparison
PRECISION = "highest"


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _sgd(params, grads, lr):
    return jax.tree.map(lambda p, g: (p - lr * g.astype(p.dtype)).astype(p.dtype),
                        params, grads)


def local_sgd_images(loss_fn, params, x, y, m, key, *, epochs, batch, lr,
                     fault=None):
    """E epochs over one client's padded samples: each epoch draws a
    permutation of the padded slots and takes ceil(n_max / batch) steps of
    ``batch`` slots (wrapping), masked to the real samples. Returns
    (params, mean step loss)."""
    n_max = y.shape[0]
    steps = max(1, -(-n_max // batch))
    used = batch // 2 if fault == "half_batch" else batch

    def epoch(carry, ekey):
        perm = jax.random.permutation(ekey, n_max)

        def step(carry, s):
            p, tot, cnt = carry
            idx = perm[(jnp.arange(batch) + s * batch) % n_max][:used]
            val, g = jax.value_and_grad(loss_fn)(p, x[idx], y[idx], m[idx])
            return (_sgd(p, g, lr), tot + val, cnt + 1.0), None

        carry, _ = jax.lax.scan(step, carry, jnp.arange(steps))
        return carry, None

    (params, tot, cnt), _ = jax.lax.scan(
        epoch, (params, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jax.random.split(key, epochs))
    return params, tot / jnp.maximum(cnt, 1.0)


def mix(trained, counts, cluster_ids, num_clusters: int, fault=None):
    """[K, ...] trained models -> the global model every client receives:
    the mean over clusters of each cluster's count-weighted average (f32
    sums, cast back to the models' dtype)."""
    if fault == "no_exchange":
        return trained
    w = counts.astype(jnp.float32)
    onehot = jax.nn.one_hot(cluster_ids, num_clusters, dtype=jnp.float32)
    tot = onehot.T @ w                                            # [L]

    def leaf(a):
        af = a.astype(jnp.float32).reshape(a.shape[0], -1)
        per = (onehot * w[:, None]).T @ af / tot[:, None]         # [L, size]
        g = jnp.mean(per, axis=0).reshape(a.shape[1:])
        return jnp.broadcast_to(g, a.shape).astype(a.dtype)

    with jax.default_matmul_precision("highest"):
        return jax.tree.map(leaf, trained)


# ---------------------------------------------------------------------------
# resident rounds (the dense engine's schedule)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "model", "rounds", "fl", "dtype", "fault", "eval_last"))
def dense_call(params, key, data, *, model, rounds, fl, dtype, fault,
               eval_last):
    """One engine call: ``rounds`` rounds from the global ``params``.
    ``fl`` is a hashable tuple (clients, clusters, per_cluster, epochs,
    batch, lr). Returns (global params, [rounds] mean losses, accuracy after
    the last round or 0)."""
    N, L, Q, E, B, lr = fl
    P = L * Q
    params = _cast(params, dtype)
    x = data["x"].astype(dtype)
    loss_fn, acc_fn = model
    local = functools.partial(local_sgd_images, loss_fn, epochs=E, batch=B,
                              lr=lr, fault=fault)
    cids = jnp.repeat(jnp.arange(L, dtype=jnp.int32), Q)

    def one_round(carry, _):
        params, key = carry
        key, kr = jax.random.split(key)
        k_sel, k_tr, _, _ = jax.random.split(kr, 4)
        sel = jax.random.permutation(k_sel, N)[:P]
        keys = jax.random.split(jax.random.fold_in(k_tr, 0), P)
        trained, ls = jax.vmap(local, in_axes=(None, 0, 0, 0, 0))(
            params, x[sel], data["y"][sel], data["mask"][sel], keys)
        mixed = mix(trained, data["counts"][sel], cids, L, fault)
        # the reported global model: the mean over the mixed client rows
        params = jax.tree.map(
            lambda a: jnp.mean(a.astype(jnp.float32), axis=0).astype(dtype),
            mixed)
        return (params, key), jnp.mean(ls)

    (params, _), losses = jax.lax.scan(one_round, (params, key), None,
                                       length=rounds)
    acc = jnp.zeros((), jnp.float32)
    if eval_last:
        accs = jax.vmap(acc_fn, in_axes=(None, 0, 0, 0))(
            params, data["test_x"].astype(dtype), data["test_y"],
            data["test_mask"])
        n = jnp.sum(data["test_mask"], axis=1)
        acc = jnp.sum(accs * n) / jnp.maximum(jnp.sum(n), 1.0)
    return params, losses, acc


# ---------------------------------------------------------------------------
# sampled rounds (K of D enrolled clients, each with its own state)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("enrolled", "active"))
def sampled_ids(key, *, enrolled, active):
    k_sel = jax.random.split(key, 4)[0]
    return jax.random.permutation(k_sel, enrolled)[:active]


@functools.partial(jax.jit, static_argnames=("model", "fl", "dtype", "fault"))
def sampled_round(starts, ids, key, data, *, model, fl, dtype, fault):
    """One window round: ``starts`` [K, ...] are the active clients' own
    states; returns (mixed [K, ...], mean loss)."""
    data_clients, L, E, B, lr = fl
    K = ids.shape[0]
    _, k_tr, _, _ = jax.random.split(key, 4)
    rows = ids % data_clients
    local = functools.partial(local_sgd_images, model[0], epochs=E, batch=B,
                              lr=lr, fault=fault)
    keys = jax.random.split(jax.random.fold_in(k_tr, 0), K)
    trained, ls = jax.vmap(local)(
        _cast(starts, dtype), data["x"][rows].astype(dtype), data["y"][rows],
        data["mask"][rows], keys)
    cids = jnp.repeat(jnp.arange(L, dtype=jnp.int32), K // L)
    return mix(trained, data["counts"][rows], cids, L, fault), jnp.mean(ls)


class SampledState:
    """The reference's client store: a table of client states (row 0 the
    initial model, then every row a round left) and, per client, the row
    that holds its state."""

    def __init__(self, init_params, enrolled: int, capacity: int):
        self.table = _table(init_params, capacity=capacity)
        self.rows = 1
        self.of_client = np.zeros((enrolled,), np.int32)

    def gather(self, ids_np):
        """[K, ...] states of the clients ``ids_np``."""
        return _take(self.table, jnp.asarray(self.of_client[ids_np]))

    def scatter(self, ids_np, mixed):
        """Each client of a round leaves it with its own mixed row."""
        K = len(ids_np)
        self.table = _put(self.table, mixed, jnp.int32(self.rows))
        self.of_client[ids_np] = self.rows + np.arange(K, dtype=np.int32)
        self.rows += K

    def change_sq(self, init_params) -> list:
        """Per leaf, the sum over every client of |state - initial|^2."""
        weights = np.bincount(self.of_client, minlength=self.table_len)
        weights[0] = 0
        return _weighted_change_sq(self.table, init_params,
                                   jnp.asarray(weights, jnp.float32))

    @property
    def table_len(self) -> int:
        return jax.tree.leaves(self.table)[0].shape[0]


@functools.partial(jax.jit, static_argnames=("capacity",))
def _table(init_params, *, capacity):
    return jax.tree.map(
        lambda a: jnp.zeros((capacity,) + a.shape, a.dtype).at[0].set(a),
        init_params)


@jax.jit
def _take(table, rows):
    return jax.tree.map(lambda t: t[rows], table)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(table, mixed, at):
    return jax.tree.map(
        lambda t, m: jax.lax.dynamic_update_slice_in_dim(t, m, at, axis=0),
        table, mixed)


@jax.jit
def _weighted_change_sq(table, init_params, weights):
    def leaf(t, a):
        d = t.astype(jnp.float32) - a.astype(jnp.float32)[None]
        w = weights.reshape((-1,) + (1,) * (t.ndim - 1))
        return jnp.sum(w * jnp.square(d))
    return [leaf(t, a) for t, a in zip(jax.tree.leaves(table),
                                       jax.tree.leaves(init_params))]


# ---------------------------------------------------------------------------
# mesh rounds (one language-model client per chip)
# ---------------------------------------------------------------------------

def local_sgd_tokens(loss_fn, params, tokens, labels, *, lr, fault=None):
    """One client's local steps: ``tokens``/``labels`` [steps, B, S]."""
    if fault == "half_batch":
        tokens = tokens[:, : tokens.shape[1] // 2]
        labels = labels[:, : labels.shape[1] // 2]

    def step(p, b):
        val, g = jax.value_and_grad(loss_fn)(p, *b)
        return _sgd(p, g, lr), val

    params, vals = jax.lax.scan(step, params, (tokens, labels))
    return params, jnp.mean(vals)


def mesh_round(f_params, tokens, labels, *, loss_fn, lr, num_clusters,
               fault=None):
    """One round over [D, ...] client states: local steps per client, then
    the cluster/global mix with equal client weights."""
    D = tokens.shape[0]
    trained, ls = jax.vmap(functools.partial(
        local_sgd_tokens, loss_fn, lr=lr, fault=fault))(f_params, tokens,
                                                        labels)
    cids = jnp.repeat(jnp.arange(num_clusters, dtype=jnp.int32),
                      D // num_clusters)
    return mix(trained, jnp.ones((D,), jnp.float32), cids, num_clusters,
               fault), jnp.mean(ls)
