"""A whole run with the timed path broken underneath reads ``correct``
false, once for each fault a cell can have: a step that returns its state
unchanged, half of each batch left out, the exchange between clients (and
between chips) left out, and an answer altered where it is produced. A
sound run of the same tiny cell reads true."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench_faults  # noqa: E402
from bench_tiny import MESH, RESIDENT, ROOT, SAMPLED  # noqa: E402


@pytest.mark.parametrize("name", [RESIDENT, SAMPLED])
def test_sound_run_is_correct(name):
    res = bench_faults.run(name)
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", bench_faults.FAULTS)
@pytest.mark.parametrize("name", [RESIDENT, SAMPLED])
def test_fault_is_caught(name, fault, monkeypatch):
    bench_faults.plant(monkeypatch, fault)
    res = bench_faults.run(name)
    assert not res["correct"], (fault, res["checks"])


def test_mesh_faults_are_caught():
    """The mesh cell on four virtual CPU devices, in a process of its own:
    the sound run is correct and every planted fault is caught."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {HERE!r})
        import pytest
        import bench_faults
        out = {{"sound": bench_faults.run({MESH!r})["correct"]}}
        for fault in bench_faults.FAULTS:
            with pytest.MonkeyPatch.context() as mp:
                bench_faults.plant(mp, fault)
                out[fault] = bench_faults.run({MESH!r})["correct"]
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out.pop("sound") is True
    assert not any(out.values()), out
