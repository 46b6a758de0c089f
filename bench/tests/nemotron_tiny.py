"""The Nemotron-H cell cut to CPU size for the tests: d_model 64, two SSM
groups of two heads, 16 routed experts of which 8 are held, top 3, a
shared expert twice a routed one's width, blocks MEMEM*E, vocabulary 256,
sequences of 64 in chunks of 16. The configuration keeps the file's two
namings in step: the published keys the reference reads and the program's
own."""
import copy
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402

CELL = "nemotron-3-nano-30b-a3b.fedp2p-mesh4"
CONFIG = os.path.join(ROOT, "bench", "configs", "nemotron-3-nano-30b-a3b.json")

#: (published key, program key or None): value
TINY = {
    ("hidden_size", "d_model"): 64,
    ("mamba_num_heads", "ssm_heads"): 4,
    ("mamba_head_dim", "ssm_head_dim"): 16,
    ("n_groups", "ssm_n_groups"): 2,
    ("ssm_state_size", "ssm_state"): 16,
    ("chunk_size", "ssm_chunk"): 16,
    ("n_routed_experts", "router_experts"): 16,
    (None, "num_experts"): 8,
    ("num_experts_per_tok", None): 3,
    ("moe_intermediate_size", "moe_d_ff"): 32,
    ("moe_shared_expert_intermediate_size", "shared_expert_d_ff"): 64,
    ("num_attention_heads", "num_heads"): 4,
    ("num_key_value_heads", "num_kv_heads"): 2,
    ("head_dim", None): 16,
    ("vocab_size", None): 256,
}


def tiny_config(config: dict, **held) -> dict:
    """``config`` at the tiny cut; ``held`` overrides the program-side
    ``num_experts`` (experts held) or ``num_layers``."""
    cfg = copy.deepcopy(config)
    for keys, v in TINY.items():
        for k in keys:
            if k:
                cfg[k] = v
    cfg.update(held)
    return cfg


def tiny_cell() -> dict:
    cell = copy.deepcopy(common.resolve_cell(CELL))
    cell["config"] = tiny_config(cell["config"])
    cell["cell"]["rounds_per_call"] = 2
    cell["traffic"].update(batch=2, seq=64, base_vocab=200, pool_rounds=6)
    cell["cell"]["round"].update(local_steps=2)
    return cell
