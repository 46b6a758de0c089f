"""The benchmark's own counts: operations per sample and per token, the
real-sample counts that padding does not inflate, the segment mix's bytes,
and the configurations' parameter counts against the program's trees."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench.common import load_json  # noqa: E402
from bench.drivers.dense_rounds import segment_mix_bytes  # noqa: E402
from bench.models import cnn, mamba2  # noqa: E402
from bench.traffic import generate  # noqa: E402

CNN = load_json(os.path.join(ROOT, "bench", "configs", "cnn-femnist.json"))
MAMBA = load_json(os.path.join(ROOT, "bench", "configs", "mamba2-130m.json"))


def test_cnn_forward_flops():
    # conv1 28*28*32*25*2, conv2 14*14*64*25*32*2, fc 3136*62*2
    assert cnn.forward_flops_per_sample(CNN) == 1_254_400 + 20_070_400 + 388_864
    assert cnn.forward_flops_per_sample(CNN) == 21_713_664
    assert cnn.train_flops_per_sample(CNN) == 3 * 21_713_664


def test_cnn_parameters_match_program():
    from repro.configs.paper_models import CNN_FEMNIST
    from repro.models.paper_nets import init_paper_net
    mine = jax.eval_shape(lambda k: cnn.init(k, CNN), jax.random.PRNGKey(0))
    prog = jax.eval_shape(lambda k: init_paper_net(k, CNN_FEMNIST),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(prog)
    assert [a.shape for a in jax.tree.leaves(mine)] == \
        [a.shape for a in jax.tree.leaves(prog)]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(mine)) == \
        CNN["parameters"] == 246_590


def test_real_samples_exclude_padding():
    spec = {"data_clients": 12, "per_client": 30, "classes_per_client": 5,
            "label_classes": 10, "noise": 0.7, "test_frac": 0.2}
    d = generate.image_clients(spec, seed=2**35 + 1, image_size=28, channels=1)
    n_tr, n_te = generate.split_sizes(spec)
    assert d["x"].shape == (12, n_tr, 28, 28, 1) and n_tr == 24
    assert d["test_x"].shape[1] == n_te == 6
    mask = np.asarray(d["mask"])
    assert (mask.sum(1) == d["counts_np"]).all()
    assert (np.asarray(d["counts"]) == d["counts_np"]).all()
    assert d["counts_np"].sum() < mask.size        # padding is not counted
    assert (np.asarray(d["x"])[mask == 0] == 0).all()
    # the same seed gives the same data
    again = generate.image_clients(spec, seed=2**35 + 1, image_size=28,
                                   channels=1)
    assert np.array_equal(np.asarray(again["x"]), np.asarray(d["x"]))


def test_mamba2_flops_per_token():
    L, d, di, n, h, p, V, q = 24, 768, 1536, 128, 24, 64, 50280, 256
    proj = d * (2 * di + 2 * n + h) + di * d
    ssd = q * n + q * h * p + 2 * h * p * n
    assert mamba2.forward_flops_per_token(MAMBA, 2048) == \
        2 * (L * (proj + ssd) + V * d)
    assert mamba2.train_flops_per_token(MAMBA, 2048) == \
        3 * mamba2.forward_flops_per_token(MAMBA, 2048)
    # close to six per parameter, with the SSD on top
    per_param = mamba2.train_flops_per_token(MAMBA, 2048) / 128_983_488
    assert 6.0 < per_param < 8.5


def test_mamba2_parameters_match_program():
    from repro.configs import get_config
    from repro.models.model import build_model
    from bench.drivers.mesh_rounds import model_config
    mcfg = model_config(MAMBA)
    assert mcfg == get_config("mamba2-130m")
    prog = jax.eval_shape(build_model(mcfg).init, jax.random.PRNGKey(0))
    mine = jax.eval_shape(lambda k: mamba2.init(k, MAMBA), jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(prog)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(mine), jax.tree.leaves(prog)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(mine)) == \
        MAMBA["parameters"]


def test_segment_mix_bytes():
    D, P = 200, 246_590
    # read x_new and x_old, write the mixed rows; write then read [1, P]
    assert segment_mix_bytes(D, P, segments=1) == 4 * (3 * D * P + 2 * P)
    assert segment_mix_bytes(D, P, segments=1) == pytest.approx(593.79e6, rel=1e-4)


def test_sampled_rows_use_the_program_layout():
    from repro.kernels.ops import pack_tree
    from bench.drivers.sampled_rounds import leaf_layout
    params = cnn.init(jax.random.PRNGKey(1), dict(CNN, hidden=8))
    flat, _ = pack_tree(jax.tree.map(lambda a: a[None], params))
    for (name, off, size), leaf in zip(leaf_layout(params),
                                       jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(flat[0, off:off + size]),
                              np.asarray(leaf).ravel()), name
