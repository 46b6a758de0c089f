"""``BENCHMARK.json`` against the files it names and the rules it keeps:
every cell's configuration, traffic mix, cell file, driver and metric
readers exist; names and units use the allowed characters; every per-layer
metric's end-to-end metric is reported where it is read; at most half the
cells take four chips; and a full check fits its time."""
import importlib
import json
import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402

B = common.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_top_level_keys_and_command():
    assert set(B) == TOP
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert os.path.getsize(common.MANIFEST) <= 64 * 1024
    assert 1 <= B["run_seconds"] <= 51


def test_entries_have_only_their_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_names_units_and_text():
    names = ([c["name"] for c in B["configs"]]
             + [w["name"] for w in B["workloads"]]
             + [m["name"] for m in B["end_to_end"] + B["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in B["workloads"]]:
        assert NAME.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in ([c["why"] for c in B["configs"]] + [c["source"] for c in B["configs"]]
                 + [w["why"] for w in B["workloads"]]
                 + [m["layer"] for m in B["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in B["configs"]}
    pairs = set()
    for w in B["workloads"]:
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = common.resolve_cell(w["name"])
        importlib.import_module(f"bench.drivers.{cell['cell']['driver']}")
        assert cell["traffic"]["kind"] in ("image_clients", "token_rounds")
        assert int(cell["cell"]["check_steps"]) >= 2
        assert cell["cell"]["limits"]
    used = {w["config"] for w in B["workloads"]}
    assert used == set(configs)
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith("bench/") and os.path.exists(os.path.join(ROOT, f))
    for m in B["per_layer"]:
        assert hasattr(importlib.import_module(f"bench.metrics.{m['name']}"),
                       "read")


def test_metric_sources_and_moves():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in B["workloads"]}
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells)
    for cell in cells:
        reported = [m for m in B["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert {"setup_s"} < {m["name"] for m in reported}
        assert any(cell in m.get("workloads", cells) for m in B["per_layer"])


def test_four_chip_share_and_check_time():
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in B["workloads"])
    assert len(four) <= max(1, len(B["workloads"]) // 2)
    # a full check of 24 cells: 2 + 14 x 24 runs of run_seconds + 60 s,
    # 2 x 90 s of compile per cell, 1200 s spare, within 43200 s
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_reduced_keys_are_not_widths():
    width = re.compile(r"(hidden|intermediate|latent|state|proj|head|_dim$"
                       r"|_rank$|expand|experts_per_tok|d_model)")
    for c in B["configs"]:
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not width.search(k), k


def test_manifest_is_json_with_no_duplicate_keys():
    def no_dups(pairs):
        keys = [k for k, _ in pairs]
        assert len(keys) == len(set(keys)), keys
        return dict(pairs)
    with open(common.MANIFEST) as f:
        json.load(f, object_pairs_hook=no_dups)
