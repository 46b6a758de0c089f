"""Configuration dataclasses for the repro framework.

Everything is a frozen dataclass so configs are hashable (usable as jit static
arguments) and safely shareable. ``ModelConfig`` is a single union-style record
covering all six architecture families; family-specific fields default to
"unused" sentinels so dense configs stay terse.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple


# ---------------------------------------------------------------------------
# Model architecture
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    """Architecture description for one model.

    Covers: dense decoder transformers (GQA/MQA, bias variants, GeGLU /
    SwiGLU / squared-ReLU MLPs), MoE (top-k routed + shared experts, MLA),
    SSM (Mamba-2 SSD), hybrid (parallel attention+SSM heads), audio and VLM
    decoder backbones.
    """

    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    vocab_size: int

    # --- attention ---
    num_heads: int = 0               # query heads; 0 => attention-free (pure SSM)
    num_kv_heads: int = 0            # KV heads for GQA/MQA; ==num_heads => MHA
    head_dim: int = 0                # 0 => d_model // num_heads
    qkv_bias: bool = False           # qwen2-style bias on q/k/v projections
    qk_norm: bool = False            # chameleon-style RMSNorm on q and k
    rope_theta: float = 10000.0
    rope_pct: float = 1.0            # nemotron uses partial rotary (0.5)
    sliding_window: int = 0          # 0 => full attention; >0 => window size
    global_layer_every: int = 0      # hybrid: every k-th layer is full-attn

    # --- MLA (deepseek-v2) ---
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MLP ---
    d_ff: int = 0
    mlp_variant: str = "swiglu"      # swiglu | geglu | squared_relu | gelu
    mlp_bias: bool = False

    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                # per-expert hidden size (0 => d_ff)
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.01
    first_dense_layers: int = 0      # deepseek: first k layers are dense
    moe_dense_d_ff: int = 0          # hidden size of those dense layers
    # router: softmax (top-k of the softmax, renormalized) | sigmoid (top-k
    # chosen on sigmoid + a selection-only bias, the router_bias leaf, and
    # weighted by the sigmoid)
    moe_router: str = "softmax"
    norm_topk_prob: bool = True      # sigmoid router: weights normalized to sum 1
    routed_scaling: float = 1.0      # sigmoid router: weights times this
    shared_expert_d_ff: int = 0      # 0 => num_shared_experts * moe_d_ff
    # experts the router scores (0 => num_experts). A layer holding a slice
    # of the experts (one chip's share) keeps the published router width:
    # it holds experts [0, num_experts) of router_experts.
    router_experts: int = 0

    # --- SSM (Mamba-2 SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    ssm_heads: int = 0               # 0 => ssm_expand * d_model // ssm_head_dim
    ssm_n_groups: int = 1            # heads sharing one B and one C
    ssm_norm_eps: float = 1e-6       # eps of the gated RMSNorm (per group)

    # --- layer pattern (hybrid stacks such as Nemotron-H) ---
    # one letter per block, each x + mixer(norm(x)): "M" Mamba-2, "E" MoE,
    # "*" attention. "" => the family's homogeneous stack. Blocks are the
    # first num_layers letters (a cut keeps the published pattern).
    layer_pattern: str = ""

    # --- hybrid (hymba) ---
    num_meta_tokens: int = 0

    # --- embeddings / misc ---
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    norm_type: str = "rmsnorm"       # rmsnorm | rmsnorm_p1 (gemma +1) | layernorm
    embed_scale: bool = False        # gemma multiplies embeddings by sqrt(d)
    logit_softcap: float = 0.0
    # audio (musicgen): number of parallel codebooks + cross-attention context
    num_codebooks: int = 0
    cross_attend: bool = False
    cross_context_len: int = 0
    cross_context_dim: int = 0
    # vlm (chameleon): fraction of sequence that is VQ image tokens (stub frontend)
    image_token_frac: float = 0.0

    dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.sliding_window is None:          # config.json's null
            object.__setattr__(self, "sliding_window", 0)
        if self.layer_pattern:
            bad = set(self.layer_pattern) - set("ME*")
            if bad or len(self.layer_pattern) < self.num_layers:
                raise ValueError(
                    f"ModelConfig {self.name}: layer_pattern needs one of "
                    f"'M', 'E', '*' per layer for {self.num_layers} layers, "
                    f"got {self.layer_pattern!r}")
        if self.num_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.num_heads and not self.num_kv_heads:
            object.__setattr__(self, "num_kv_heads", self.num_heads)

    # --- derived sizes -------------------------------------------------
    @property
    def attn_free(self) -> bool:
        return self.num_heads == 0

    @property
    def d_inner_ssm(self) -> int:
        if self.ssm_heads:
            return self.ssm_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def ssm_num_heads(self) -> int:
        return self.d_inner_ssm // self.ssm_head_dim

    @property
    def pattern(self) -> str:
        """The blocks this configuration builds ("" without a pattern)."""
        return self.layer_pattern[:self.num_layers]

    @property
    def num_router_experts(self) -> int:
        return self.router_experts or self.num_experts

    def reduced(self, *, num_layers: int = 2, max_d_model: int = 256,
                max_experts: int = 4, vocab: int = 512) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests (per the brief:
        <=2 layers, d_model<=512, <=4 experts)."""
        d = min(self.d_model, max_d_model)
        scale = d / self.d_model
        heads = max(1, min(self.num_heads, 4)) if self.num_heads else 0
        kv = 0
        if heads:
            kv = max(1, min(self.num_kv_heads, heads))
            while heads % kv:
                kv -= 1
        changes = dict(
            num_layers=num_layers,
            d_model=d,
            vocab_size=min(self.vocab_size, vocab),
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=(d // heads if heads else 0),
            d_ff=max(8, int(self.d_ff * scale)) if self.d_ff else 0,
            first_dense_layers=min(self.first_dense_layers, 1),
        )
        if self.num_experts:
            ne = min(self.num_experts, max_experts)
            changes.update(
                num_experts=ne,
                num_experts_per_tok=min(self.num_experts_per_tok, ne),
                num_shared_experts=min(self.num_shared_experts, 1),
                moe_d_ff=max(8, int((self.moe_d_ff or self.d_ff) * scale)),
                moe_dense_d_ff=max(8, int((self.moe_dense_d_ff or self.d_ff or 64) * scale)),
                router_experts=ne if self.router_experts else 0,
                shared_expert_d_ff=(max(8, int(self.shared_expert_d_ff * scale))
                                    if self.shared_expert_d_ff else 0),
            )
        if self.use_mla:
            changes.update(kv_lora_rank=32, q_lora_rank=0,
                           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                           head_dim=24)
        if self.ssm_state:
            changes.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
            if self.ssm_heads:
                changes.update(ssm_heads=min(self.ssm_heads, 8),
                               ssm_n_groups=min(self.ssm_n_groups, 2))
        if self.num_meta_tokens:
            changes.update(num_meta_tokens=8)
        if self.cross_attend:
            changes.update(cross_context_len=8, cross_context_dim=d)
        if self.sliding_window:
            changes.update(sliding_window=64)
        return dataclasses.replace(self, **changes)


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                        # train | prefill | decode
    # decode_32k / long_500k: seq_len is the KV-cache length, one new token.


# ---------------------------------------------------------------------------
# Mesh / distribution
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    data: int = 16
    model: int = 16
    pod: int = 1                     # >1 => multi-pod

    @property
    def num_devices(self) -> int:
        return self.pod * self.data * self.model

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.pod > 1 else ("data", "model")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.pod, self.data, self.model) if self.pod > 1 else (self.data, self.model)


# ---------------------------------------------------------------------------
# Federated learning protocol
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FLConfig:
    """FedP2P / FedAvg protocol parameters (paper §3.1, Algo 1 & 2)."""

    num_clients: int = 100           # N
    num_clusters: int = 10           # L (FedP2P local P2P networks)
    devices_per_cluster: int = 10    # Q
    participation: int = 10          # P for FedAvg (=|Z|); FedP2P uses L*Q
    rounds: int = 100                # T
    local_epochs: int = 20           # E (paper §4.2)
    batch_size: int = 10             # O
    lr: float = 0.01                 # eta
    straggler_rate: float = 0.0      # fraction of selected devices that drop
    sync_period: int = 1             # global sync every k rounds (1 = paper)
    seed: int = 0
    # any repro.protocols registry name (fedavg | fedp2p | gossip |
    # fedp2p_topo | ...); validated at dispatch — unknown names raise
    algorithm: str = "fedp2p"
    # §5: upgrade the algorithm to its "_topo" hop-aware variant when one
    # is registered (fedp2p -> fedp2p_topo)
    topology_aware: bool = False
    # any repro.compression registry name (none | bf16 | int8 | topk):
    # the lossy wire format every exchanged model update goes through.
    # "none" keeps rounds bit-for-bit the uncompressed program.
    codec: str = "none"
    # which mixing lowering the engines run (dense | sparse | auto):
    # "dense" = the [D, D] mixing-matrix oracle (bit-for-bit the pre-spec
    # program), "sparse" = the protocol's structured MixingSpec kernels
    # (O(D·n) per round, raises for spec-less protocols), "auto" = sparse
    # exactly where a spec exists.
    mix_path: str = "auto"
    # --- sampled participation (SampledEngine / ClientStateStore) ---
    # D — the ENROLLED client population behind a protocols.store state
    # store. 0 (default) = resident mode: num_clients is the whole
    # population and every engine behaves exactly as before. When set,
    # each round only gathers/trains/mixes/scatters a K-sized active
    # window of the [D, sum(sizes)] store.
    num_enrolled: int = 0
    # K — active clients per sampled round. 0 (default) = the protocol's
    # own num_participants(fl). Must satisfy K <= num_enrolled (validated
    # below) and K >= the protocol's cluster count (validated at engine
    # construction — protocols.base.validate_participation).
    participants_per_round: int = 0
    # repro.protocols participation-strategy registry name (uniform |
    # pareto): how the K-sized active set is drawn from the D enrolled
    # clients. "uniform" is the paper's uniform-without-replacement
    # sampling; "pareto" biases toward resource-rich clients under the
    # participation_rate availability cap (SNIPPETS.md snippet 1).
    participation_strategy: str = "uniform"
    # fraction of enrolled clients available in any given round (the
    # Pareto strategy's per-round Bernoulli availability cap; uniform
    # ignores it). Must lie in (0, 1].
    participation_rate: float = 1.0
    # --- fault tolerance (repro.faults / protocols.store) ---
    # checkpoint-tier read resilience: a failed load_leaves / base-row
    # read is retried this many times before the error propagates
    # (0 = fail fast). Retries only fire on transient OSErrors —
    # CheckpointCorruptionError is permanent and never retried.
    store_read_retries: int = 2
    # base seconds of the exponential backoff between read retries
    # (retry k sleeps store_read_backoff * 2**k).
    store_read_backoff: float = 0.05
    # seconds the pipelined engine waits on a prefetch handle before
    # abandoning it and falling back to a synchronous gather (counted as
    # a prefetch_fallback). 0 = wait forever, the pre-fault behavior.
    prefetch_timeout: float = 0.0

    def __post_init__(self):
        if self.num_enrolled < 0:
            raise ValueError(
                f"FLConfig: num_enrolled must be >= 0 (0 = resident mode), "
                f"got {self.num_enrolled}")
        if self.participants_per_round < 0:
            raise ValueError(
                f"FLConfig: participants_per_round must be >= 0 (0 = the "
                f"protocol's own participant count), got "
                f"{self.participants_per_round}")
        if (self.num_enrolled and self.participants_per_round
                and self.participants_per_round > self.num_enrolled):
            raise ValueError(
                f"FLConfig: participants_per_round="
                f"{self.participants_per_round} active clients exceed the "
                f"num_enrolled={self.num_enrolled} enrolled population; a "
                "sampled round needs K <= D")
        if not (0.0 < self.participation_rate <= 1.0):
            raise ValueError(
                f"FLConfig: participation_rate must lie in (0, 1], got "
                f"{self.participation_rate}")
        if self.store_read_retries < 0:
            raise ValueError(
                f"FLConfig: store_read_retries must be >= 0, got "
                f"{self.store_read_retries}")
        if self.store_read_backoff < 0:
            raise ValueError(
                f"FLConfig: store_read_backoff must be >= 0, got "
                f"{self.store_read_backoff}")
        if self.prefetch_timeout < 0:
            raise ValueError(
                f"FLConfig: prefetch_timeout must be >= 0 (0 = wait "
                f"forever), got {self.prefetch_timeout}")

    @property
    def enrolled(self) -> int:
        """D — the client population a state store holds: ``num_enrolled``
        when sampled participation is on, else ``num_clients``."""
        return self.num_enrolled or self.num_clients


# ---------------------------------------------------------------------------
# Training / serving drivers
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"         # sgd | momentum | adamw
    lr: float = 3e-4
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    momentum: float = 0.9
    schedule: str = "cosine"         # constant | cosine | warmup_cosine
    warmup_steps: int = 100
    total_steps: int = 1000
    grad_clip: float = 1.0
    remat: bool = True
    microbatches: int = 1        # gradient-accumulation steps per batch
    seed: int = 0


@dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0         # 0 => greedy
    window: int = 8192               # sliding-window size used for long_500k


@dataclass(frozen=True)
class RunConfig:
    """Top-level bundle handed to launchers."""
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    fl: FLConfig = field(default_factory=FLConfig)
