"""The comparison that decides ``correct``.

Both sides give the same readings of the first steps a cell's object is
driven through: every round's mean training loss, the accuracy evaluated at
the end of each call (where the call evaluates), and per leaf of the
federated state the norm of its change after the first step and after the
last. Four numbers come of them, each held to the cell's own limit:

* ``loss_gap``   the largest gap of a round's loss, over the larger of that
  round's reference loss and the median round's;
* ``update_gap`` the worst leaf's gap of the first step's change, between
  the program's norm and the reference's, over the larger of that leaf's
  reference norm and the median leaf's;
* ``change_gap`` the same for the change after all the steps;
* ``acc_gap``    the largest absolute gap of an evaluated accuracy.

Leaves whose first change in the reference is under a thousandth of the
median leaf's move by rounding alone and are left out of both gaps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: a leaf moves by rounding alone below this share of the median leaf
STILL_LEAF = 1e-3


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _sq_change(new, old):
    return [jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def change_sq(new, old) -> dict:
    """Per leaf, the sum of squares of ``new - old`` (host floats)."""
    sq = jax.device_get(_sq_change(new, old))
    return dict(zip(leaf_names(old), (float(v) for v in sq)))


def norms(sq: dict) -> dict:
    return {k: float(np.sqrt(v)) for k, v in sq.items()}


def _moving(ref_first: dict) -> list:
    med = float(np.median(list(ref_first.values())))
    return [k for k, v in ref_first.items() if v >= STILL_LEAF * med]


def leaf_gap(prog: dict, ref: dict, leaves) -> float:
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in leaves)


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of one run from the two sides' readings."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    floor = np.maximum(np.abs(lr), np.median(np.abs(lr)))
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / floor))}
    leaves = _moving(ref["d1"])
    out["update_gap"] = leaf_gap(prog["d1"], ref["d1"], leaves)
    out["change_gap"] = leaf_gap(prog["d3"], ref["d3"], leaves)
    if prog.get("acc") is not None:
        out["acc_gap"] = float(np.max(np.abs(np.asarray(prog["acc"])
                                             - np.asarray(ref["acc"]))))
    return out


def verdict(nums: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}); a number that is not finite,
    or has no limit, fails."""
    table, ok = {}, True
    for name, value in nums.items():
        limit = limits.get(name)
        good = limit is not None and bool(np.isfinite(value)) and value <= limit
        ok &= good
        table[name] = {"value": value, "limit": limit}
    return ok, table
