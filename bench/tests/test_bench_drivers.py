"""Each driver at a CPU size, through the driver's own functions: it
reaches the program's entry, its first steps' readings agree with the
plain reference, the bfloat16 control and the planted faults read far
off, and ``bench/run.py`` refuses to run without a TPU."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench_tiny  # noqa: E402
from bench_tiny import MESH, RESIDENT, ROOT, SAMPLED, tiny  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import check, drivers  # noqa: E402

SEED = 2**33 + 17


def _drive(name):
    cell = tiny(name)
    d = drivers.load(cell["cell"]["driver"])(
        cell["config"], cell["traffic"], cell["cell"], SEED, jax.devices())
    d.setup()
    prog = d.readings(int(cell["cell"]["check_steps"]))
    d.free()
    return d, prog, cell


@pytest.mark.parametrize("name", [RESIDENT, SAMPLED])
def test_driver_matches_reference(name):
    d, prog, cell = _drive(name)
    steps = int(cell["cell"]["check_steps"])
    assert d.calls == steps
    assert len(prog["losses"]) == steps * cell["cell"]["rounds_per_call"]
    ref = d.reference(steps)
    nums = check.numbers(prog, ref)
    ok, _ = check.verdict(nums, cell["cell"]["limits"])
    assert ok, nums
    assert nums["loss_gap"] < 1e-5 and nums["update_gap"] < 1e-4
    # the control and the planted faults are caught
    for kw in ({"dtype": jnp.bfloat16}, {"fault": "half_batch"},
               {"fault": "no_exchange"}):
        bad, _ = check.verdict(check.numbers(d.reference(steps, **kw), ref),
                               cell["cell"]["limits"])
        assert not bad, kw
    assert d.required_flops_per_call() > 0
    assert d.mix_bytes_per_round() > 0


def test_mesh_driver_on_four_devices():
    """The mesh driver over four virtual CPU devices, in a process of its
    own (this one holds a single CPU device)."""
    code = textwrap.dedent(f"""
        import os, sys, json
        sys.path.insert(0, {HERE!r})
        from bench_tiny import tiny, MESH
        import jax, jax.numpy as jnp
        from bench import check, drivers
        cell = tiny(MESH)
        d = drivers.load(cell["cell"]["driver"])(
            cell["config"], cell["traffic"], cell["cell"], {SEED},
            jax.devices())
        d.setup()
        shards = {{s.device for leaf in jax.tree.leaves(d.f)
                   for s in leaf.addressable_shards}}
        prog = d.readings(3)
        d.free()
        ref = d.reference(3)
        out = {{"n_dev": len(shards), "calls": d.calls,
               "ok": check.numbers(prog, ref),
               "control": check.numbers(d.reference(3, dtype=jnp.bfloat16), ref),
               "no_exchange": check.numbers(d.reference(3, fault="no_exchange"), ref),
               "limits": cell["cell"]["limits"]}}
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["n_dev"] == 4 and out["calls"] == 3
    assert check.verdict(out["ok"], out["limits"])[0], out["ok"]
    assert not check.verdict(out["control"], out["limits"])[0]
    assert not check.verdict(out["no_exchange"], out["limits"])[0]


@pytest.mark.parametrize("name", [RESIDENT, MESH])
def test_run_refuses_without_tpu(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert "refusing to run" in res.stderr
    assert not any(line.startswith("{") for line in res.stdout.splitlines())


def test_run_refuses_outside_a_checkout(tmp_path):
    """A directory that holds only the benchmark's own files has no
    program to run: no result."""
    import shutil
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", RESIDENT, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert not any(line.startswith("{") for line in res.stdout.splitlines())


assert bench_tiny
