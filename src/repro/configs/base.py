"""Config system: architecture + input-shape + run configs, with a registry.

Every assigned architecture gets one module in this package defining
``CONFIG = ModelConfig(...)`` with the exact assigned hyperparameters and a
source citation. ``reduced()`` derives the CPU smoke-test variant of the same
family (≤2 layers, d_model ≤ 512, ≤4 experts).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-style Multi-head Latent Attention dims."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm
    source: str                 # citation for the assigned config
    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    # attention flavor
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0      # 0 = full causal; >0 = window size
    long_context_window: int = 16_384  # window used for long_500k decode
    # ffn flavor
    act: str = "swiglu"          # swiglu | geglu | gelu
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0            # expert intermediate size
    n_dense_layers: int = 0      # leading dense layers (DeepSeek: 3)
    router_aux_coef: float = 0.001
    capacity_factor: float = 1.25
    # MLA / MTP (DeepSeek)
    mla: Optional[MLAConfig] = None
    mtp: bool = False            # multi-token-prediction extra head
    # SSM
    ssm_kind: str = ""           # rwkv6 | mamba (hybrid uses mamba)
    ssm_state: int = 0
    # enc-dec / VLM
    encoder_layers: int = 0
    cross_attn_every: int = 0    # vlm: 1 cross-attn layer per this many self layers
    n_frontend_tokens: int = 0   # stubbed modality tokens (audio frames / image patches)
    # misc
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    # ---- derived ----
    @property
    def kv_groups(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant of the same family: tiny dims, same structure."""
        kw = dict(
            name=self.name + "-smoke",
            n_layers=2,
            d_model=min(self.d_model, 128),
            d_ff=min(self.d_ff, 256),
            vocab=min(self.vocab, 512),
            dtype="float32",
            long_context_window=64,
        )
        if self.n_heads:
            kw["n_heads"] = min(self.n_heads, 4)
            kw["n_kv_heads"] = min(self.n_kv_heads, 2)
            kw["head_dim"] = min(self.head_dim, 32) if self.head_dim else 0
        if self.n_experts:
            kw["n_experts"] = min(self.n_experts, 4)
            kw["top_k"] = min(self.top_k, 2)
            kw["moe_d_ff"] = min(self.moe_d_ff, 128)
            kw["n_dense_layers"] = min(self.n_dense_layers, 1)
            # ample capacity so smoke tests see no token dropping (capacity
            # drops legitimately differ between batched prefill and decode)
            kw["capacity_factor"] = 4.0
        if self.mla is not None:
            kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                                  qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
        if self.ssm_state:
            kw["ssm_state"] = min(self.ssm_state, 8)
        if self.encoder_layers:
            kw["encoder_layers"] = 2
        if self.cross_attn_every:
            kw["cross_attn_every"] = 2
        if self.n_frontend_tokens:
            kw["n_frontend_tokens"] = 16
        if self.sliding_window:
            kw["sliding_window"] = 32
        return self.replace(**kw)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass
class FedZOConfig:
    """Paper Algorithm 1 hyperparameters."""
    n_devices: int = 50        # N
    n_participating: int = 10  # M (<= N); == N means full participation
    local_iters: int = 5       # H
    lr: float = 1e-3           # eta
    mu: float = 1e-3           # smoothing step size
    b1: int = 25               # data minibatch size
    b2: int = 20               # number of perturbation directions
    estimator: str = "sphere"  # sphere (paper) | gaussian | rademacher | coordinate
    central: bool = False      # two-sided difference (O(mu^2) bias, +1 query)
    direction_dtype: str = "float32"  # bfloat16 halves perturbation HBM traffic
    # flat-buffer hot path (DESIGN.md §7): fuse perturb/update into Pallas
    # streaming kernels over one padded 1-D parameter buffer, directions
    # regenerated in-kernel from the counter convention
    flat_params: bool = False
    # direction convention for the *pytree* path: "tree" (per-leaf threefry,
    # the original) or "counter" (the flat path's convention — used to prove
    # old-vs-new trajectory equivalence). The flat path is always "counter".
    # The batched-direction (wide) path additionally accepts "block": one
    # PRNG call per iterate for the whole [b2, n_pad] direction block.
    direction_conv: str = "tree"
    # batched-direction ("wide") local phase for the simulation engine
    # (repro.sim, DESIGN.md §9): materialize each iterate's b2 directions as
    # ONE [b2, n_pad] block, run the b2 perturbed forwards as one vmap, and
    # apply the update as one matvec. Statistically identical to the loop
    # estimator; bit-identical directions when direction_conv="tree".
    batch_directions: bool = False
    # PRNG implementation for the simulation engine's key chain
    # (threefry2x32 | rbg | unsafe_rbg). threefry is the default everywhere;
    # rbg/unsafe_rbg trade threefry's splittability guarantees for ~2-4x
    # faster in-scan direction generation (simulation-scale only).
    prng_impl: str = "threefry2x32"
    # kernel grid rows per block; 0 = sized from d (blocks of at most 512
    # rows, utils/flatparams.flat_geometry)
    flat_block_rows: int = 0
    server_momentum: float = 0.0  # FedOpt-style momentum on aggregated deltas
    seed: int = 0
    # AirComp (Section IV); snr_db=None disables the channel simulation
    aircomp: bool = False
    snr_db: float = 0.0        # P / sigma_w^2
    h_min: float = 0.8
    # channel-truncation scheduling (Sec. IV-A): draw Rayleigh channels per
    # round and exclude clients with |h| < h_min from the aggregation (mask
    # into both the mean and Δ_max; m_effective reported per round)
    channel_schedule: bool = False
    # wireless scenario model (sim/channel.py, DESIGN.md §16): a
    # ``sim.ChannelModel`` makes the channel a scanned process — per-client
    # AR(1) time-correlated fading riding the experiment carry (scheduling
    # draws come from the chain instead of the i.i.d. Rayleigh draw) and
    # optional per-client energy budgets gating participation. None (the
    # default) keeps today's i.i.d. draw bit-exactly. Typed Any to avoid an
    # import cycle; hashable (frozen dataclass), so it sweeps as a static
    # run_sweep axis.
    channel_model: object = None
    # FedAvg-style size-weighted aggregation: weight each sampled client's
    # delta by n_i/n (its true row count over the sampled total) instead of
    # the uniform 1/M — realistic for the uneven/label-skew partitions of
    # the gradient-free workloads (repro.workloads). Threads through every
    # aggregation path incl. masked/AirComp via a weighted mask_stats.
    weight_by_size: bool = False
    # beyond-paper: upload {seeds, coefficients} instead of dense deltas
    delta_compression: str = "dense"  # dense | seed
    # algorithm strategy (core/strategy.py): fedzo (paper) | fedavg |
    # fedprox | feddyn | scaffold — the registry's composable round
    # decomposition. The engine, server, and sweeps all resolve this field
    # unless an explicit strategy= is passed.
    strategy: str = "fedzo"
    # ZO-FedProx proximal weight: local loss + (prox_mu/2)·‖x − x_t‖².
    # 0 reduces to FedZO bit-exactly (the penalty is statically elided).
    prox_mu: float = 0.0
    # ZO-FedDyn regularizer α (Acar et al. 2021): local loss
    # − ⟨h_i, x⟩ + (α/2)·‖x − x_t‖² with per-client duals h_i and the
    # server correction x ← x̄ − h/α. 0 reduces to FedZO bit-exactly.
    dyn_alpha: float = 0.0
    # trajectory-informed surrogate estimator (direction_conv="surrogate",
    # FedZOO-style, arXiv 2308.04077): per local iterate only
    # ceil(b2·surrogate_fraction) fresh ZO queries are paid; the update
    # direction is the EW blend g ← β·g + (1−β)·g_fresh over the iterate
    # history. Requires cfg.batch_directions (the wide phase).
    surrogate_beta: float = 0.5
    surrogate_fraction: float = 0.5
