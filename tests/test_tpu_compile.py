"""Compile every mixing kernel natively (``interpret=False``) for a described
TPU v5e at the paper CNN's width — the FEMNIST population of D=200 clients
with 246,590 parameters each — without a chip attached. Mosaic refuses
misaligned block shapes and VMEM overruns here, in about two seconds per
kernel, instead of on the chip.

The topology is described inside a module fixture (never while a module is
imported): only one process may hold the TPU library, so only the worker
that runs these tests may load it. Keep every such compile in this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fed_aggregate import fed_aggregate
from repro.kernels.fed_mix import fed_mix
from repro.kernels.fed_mix_q import fed_mix_q
from repro.kernels.fed_mix_sparse import fed_mix_matching, fed_mix_segment

D = 200                      # pseudo-FEMNIST population (paper §4.1)
P = 246_590                  # cnn-femnist parameters per client
CHUNK = 256                  # Int8Codec default chunk
PQ = P + (-P) % CHUNK        # int8 wire record width
L = 10                       # FedP2P clusters


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _cases(sh):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    mix = (s((D, D)), s((D, D)))
    rows = (s((D, P)), s((D, P)))
    return {
        "fed_mix": (lambda *a: fed_mix(*a, interpret=False), mix + rows),
        "fed_mix_q": (
            lambda *a: fed_mix_q(*a, chunk=CHUNK, interpret=False),
            mix + (s((D, PQ), jnp.int8), s((D, PQ // CHUNK)), s((D, P)))),
        "fed_mix_segment": (
            lambda *a: fed_mix_segment(*a, num_segments=L, interpret=False),
            (s((D,), jnp.int32), s((D,)), s((D,))) + rows),
        "fed_mix_matching": (
            lambda *a: fed_mix_matching(*a, interpret=False),
            (s((2, D), jnp.int32), s((D,))) + rows),
        "fed_aggregate": (
            lambda *a: fed_aggregate(*a, interpret=False),
            (s((D, P)), s((D,)))),
    }


@pytest.mark.parametrize("name", ["fed_mix", "fed_mix_q", "fed_mix_segment",
                                  "fed_mix_matching", "fed_aggregate"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    # a native Mosaic kernel, not the interpreter's unrolled XLA loop
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16 * 2**30
