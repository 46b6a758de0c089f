"""``chip_smoke.py``'s phases rehearsed on the CPU at a tiny size: the same
entry points, the same checks, with the Pallas kernels in interpret mode;
the four-device mesh phase runs in a subprocess over four virtual host
devices. The script itself must refuse to run its phases off a TPU."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.paper_models import CNN_FEMNIST

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
SRC = os.path.join(ROOT, "src")

#: the FEMNIST CNN at a CPU-sized hidden width (the chip runs hidden=64)
TINY_CNN = dataclasses.replace(CNN_FEMNIST, hidden=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_rows_ok(rows):
    assert rows
    for row in rows:
        assert row["ok"], row
        assert row["platform"] == "cpu"


def test_paper_phase_tiny(smoke):
    rows = list(smoke.phase_paper(net=TINY_CNN, num_clients=20,
                                  per_client=20, rounds=2))
    assert len(rows) == 2 * len(smoke.PAPER_VARIANTS)
    _assert_rows_ok(rows)
    # interpret mode on the CPU: no Mosaic custom call in the round program
    assert all("native=False" in r["check"] for r in rows)


def test_lm_phase_tiny(smoke):
    rows = list(smoke.phase_lm(reduced=True, num_clients=4, rounds=2,
                               local_steps=2, batch=2, seq_len=16))
    _assert_rows_ok(rows)


def test_sampled_phase_tiny(smoke):
    rows = list(smoke.phase_sampled(net=TINY_CNN, num_enrolled=64,
                                    active=16, data_clients=20,
                                    per_client=20, rounds=3))
    assert [r["phase"].rsplit("/", 1)[-1] for r in rows] == [
        "depth1", "depth2", "depth2==depth1"]
    _assert_rows_ok(rows)


def test_mesh_phase_four_host_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        rows = list(smoke.phase_mesh(reduced=True, rounds=2, local_steps=2,
                                     batch=2, seq_len=16))
        assert len(rows) == 2, rows
        for row in rows:
            assert row["ok"], row
            assert "devices=4 spread=True" in row["check"], row
        print("OK")
    """)
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert "OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]


def test_main_refuses_a_host_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, env=env, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert "phase=" not in out.stdout and '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("env_dir", [None, "/deployment/jax-cache"])
def test_compile_cache_dir_comes_from_outside(env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and no other directory is set;
    without it the cache is the checkout's fixed .jax_cache."""
    code = textwrap.dedent("""
        import jax
        from repro.launch.cache import DEFAULT_DIR, enable_compile_cache
        path = enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir, path
        print(path, DEFAULT_DIR)
    """)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    path, default = out.stdout.split()
    expect = env_dir or os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert path == expect
    assert default == os.path.join(os.path.realpath(ROOT), ".jax_cache")
