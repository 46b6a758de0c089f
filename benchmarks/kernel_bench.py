"""Kernel microbench: us/call of the pure-jnp oracle paths on CPU (the
Pallas kernels themselves are TPU-targeted; interpret mode timing is not
meaningful, so we bench the oracles and verify kernels once)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import wallclock
from repro.kernels import ref
from repro.kernels.fed_aggregate import fed_aggregate
from repro.kernels.fed_mix import fed_mix
from repro.kernels.fed_mix_sparse import fed_mix_matching, fed_mix_segment


def run(quick: bool = True):
    rows = []
    key = jax.random.PRNGKey(0)
    # fed_aggregate: aggregation of 16 client replicas of a 10M-param model
    n, d = 16, (2_000_000 if quick else 10_000_000)
    x = jax.random.normal(key, (n, d), jnp.float32)
    w = jnp.ones((n,)) / n
    f_ref = jax.jit(ref.fed_aggregate_ref)
    rows.append((f"kernel/fed_aggregate_ref/{n}x{d}",
                 wallclock(f_ref, x, w), "jnp oracle (XLA:CPU)"))
    out_k = fed_aggregate(x[:, :4096], w, interpret=True)
    ok = bool(jnp.allclose(out_k, ref.fed_aggregate_ref(x[:, :4096], w),
                           rtol=1e-4))
    rows.append(("kernel/fed_aggregate_pallas_interpret_match", float(ok),
                 "1.0 = matches oracle"))

    # fed_mix: one round of fused dense mixing, O = Mn @ Xn + Mo @ Xo
    ks = jax.random.split(key, 3)
    mn = jax.random.uniform(ks[0], (n, n)) / n
    mo = jax.random.uniform(ks[1], (n, n)) / n
    x_old = jax.random.normal(ks[2], (n, d), jnp.float32)
    f_mix = jax.jit(ref.fed_mix_ref)
    rows.append((f"kernel/fed_mix_ref/{n}x{d}",
                 wallclock(f_mix, mn, mo, x, x_old), "jnp oracle (XLA:CPU)"))
    out_m = fed_mix(mn, mo, x[:, :4096], x_old[:, :4096], interpret=True)
    ok = bool(jnp.allclose(out_m,
                           ref.fed_mix_ref(mn, mo, x[:, :4096],
                                           x_old[:, :4096]), rtol=1e-4))
    rows.append(("kernel/fed_mix_pallas_interpret_match", float(ok),
                 "1.0 = matches oracle"))

    # fed_mix_sparse: the structured-sparse mixing fast path, swept over the
    # client count D (the D-scaling column — dense grows O(D²·n), the
    # segment/matching oracles O(D·n); speedup_vs_dense is the tracked ratio)
    import numpy as np
    rng = np.random.default_rng(0)
    n_cols = 2048 if quick else 8192
    f_seg = jax.jit(lambda c, a, b2, x, y: ref.fed_mix_segment_ref(
        c, a, b2, x, y, num_segments=8))
    f_match = jax.jit(ref.fed_mix_matching_ref)
    for D in (64, 256, 1024) if quick else (64, 256, 1024, 4096):
        cids = jnp.asarray(np.arange(D, dtype=np.int32) % 8)
        wn = jnp.asarray(rng.uniform(0, 1, D).astype(np.float32))
        wo = jnp.asarray(rng.uniform(0, 1, D).astype(np.float32))
        xn_d = jnp.asarray(rng.normal(size=(D, n_cols)).astype(np.float32))
        xo_d = jnp.asarray(rng.normal(size=(D, n_cols)).astype(np.float32))
        seg_us = wallclock(f_seg, cids, wn, wo, xn_d, xo_d)
        rows.append((f"kernel/fed_mix_segment_ref/D{D}x{n_cols}",
                     seg_us, "jnp oracle (XLA:CPU), L=8 clusters"))
        perms = jnp.asarray(
            np.stack([rng.permutation(D), rng.permutation(D)]
                     ).astype(np.int32))
        sv = jnp.asarray((rng.random(D) > 0.1).astype(np.float32))
        rows.append((f"kernel/fed_mix_matching_ref/D{D}x{n_cols}",
                     wallclock(f_match, perms, sv, xn_d, xo_d),
                     "jnp oracle (XLA:CPU), 2 stages"))
        if D <= 1024:      # dense comparison column: O(D²·n) — the wall
            mn_d = jnp.asarray(rng.uniform(0, 1, (D, D)).astype(np.float32)
                               / D)
            dense_us = wallclock(f_mix, mn_d, mn_d, xn_d, xo_d)
            rows.append((f"kernel/fed_mix_ref/D{D}x{n_cols}", dense_us,
                         "dense oracle at same (D, n)"))
            rows.append((f"kernel/fed_mix_segment_speedup_vs_dense/D{D}",
                         dense_us / max(seg_us, 1e-9),
                         "sparse fast-path gain at this D"))
    # interpret-mode kernels vs oracles (verified once, small shapes)
    cids_s = jnp.asarray(np.arange(16, dtype=np.int32) % 4)
    w_s = jnp.asarray(rng.uniform(0, 1, 16).astype(np.float32))
    xs_n = jnp.asarray(rng.normal(size=(16, 300)).astype(np.float32))
    xs_o = jnp.asarray(rng.normal(size=(16, 300)).astype(np.float32))
    out_s = fed_mix_segment(cids_s, w_s, w_s, xs_n, xs_o, num_segments=4,
                            interpret=True)
    ok = bool(jnp.allclose(out_s, ref.fed_mix_segment_ref(
        cids_s, w_s, w_s, xs_n, xs_o, num_segments=4), rtol=1e-4, atol=1e-5))
    rows.append(("kernel/fed_mix_segment_pallas_interpret_match", float(ok),
                 "1.0 = matches oracle"))
    perm_s = jnp.asarray(rng.permutation(16).astype(np.int32))[None]
    sv_s = jnp.asarray((rng.random(16) > 0.3).astype(np.float32))
    out_m2 = fed_mix_matching(perm_s, sv_s, xs_n, xs_o, interpret=True)
    ok = bool(jnp.allclose(out_m2, ref.fed_mix_matching_ref(
        perm_s, sv_s, xs_n, xs_o), rtol=1e-4, atol=1e-5))
    rows.append(("kernel/fed_mix_matching_pallas_interpret_match", float(ok),
                 "1.0 = matches oracle"))

    b, h, s, hd = 1, 4, (1024 if quick else 4096), 64
    q = jax.random.normal(key, (b, h, s, hd)) * 0.5
    k = jax.random.normal(key, (b, h, s, hd)) * 0.5
    v = jax.random.normal(key, (b, h, s, hd)) * 0.5
    f_fa = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    rows.append((f"kernel/flash_attention_ref/b{b}h{h}s{s}",
                 wallclock(f_fa, q, k, v), "jnp oracle"))

    bs, ss, hh, p, nn = 2, (512 if quick else 2048), 4, 64, 64
    x2 = jax.random.normal(key, (bs, ss, hh, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(key, (bs, ss, hh)))
    A = -jnp.exp(jax.random.normal(key, (hh,)) * 0.3)
    B = jax.random.normal(key, (bs, ss, nn)) * 0.5
    C = jax.random.normal(key, (bs, ss, nn)) * 0.5
    from repro.models.ssm import ssd_chunked
    f_ssd = jax.jit(lambda *a: ssd_chunked(*a, 128))
    rows.append((f"kernel/ssd_chunked/b{bs}s{ss}",
                 wallclock(f_ssd, x2, dt, A, B, C), "chunked jnp (kernel oracle)"))
    return rows


def main():
    from benchmarks.common import print_rows
    rows = run()
    print_rows(rows)
    return rows


if __name__ == "__main__":
    main()
