"""The benchmark's cells cut to CPU size for the tests: the same files,
drivers and limits, with a small CNN or SSM, few clients and short
sequences."""
import copy
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402

RESIDENT = "cnn-femnist.fedp2p-resident"
SAMPLED = "cnn-femnist.fedp2p-sampled"
MESH = "mamba2-130m.fedp2p-mesh4"


def tiny(name: str) -> dict:
    cell = copy.deepcopy(common.resolve_cell(name))
    spec = cell["cell"]
    spec["rounds_per_call"] = 2
    if name in (RESIDENT, SAMPLED):
        cell["config"]["hidden"] = 8
        cell["traffic"].update(per_client=30)
        spec["round"].update(clusters=2, active=10)
        cell["traffic"]["data_clients"] = 10 if name == RESIDENT else 30
        if name == SAMPLED:
            spec["round"]["enrolled"] = 30
    else:
        cell["config"].update(num_layers=2, d_model=64, vocab_size=256,
                              ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
        cell["traffic"].update(batch=2, seq=64, pool_rounds=6)
        spec["round"].update(local_steps=2)
    return cell
