"""Configuration system for the repro framework.

Every job in the platform — a training run, a serving instance, a replay
simulation, a map-generation pipeline — is described by a small set of frozen
dataclasses.  Architecture configs (one per assigned architecture) live in
``repro.configs.*`` and are registered into :data:`ARCH_REGISTRY`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration (GShard-style capacity routing)."""

    num_experts: int
    top_k: int
    expert_d_ff: int
    num_shared_experts: int = 0
    shared_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 1e-2
    router_z_coef: float = 1e-3
    # 'expert': shard the expert axis over the model mesh axis (needs E % tp == 0)
    # 'ffn'   : shard each expert's FFN dim over the model mesh axis
    shard_mode: str = "expert"
    # dispatch groups (GShard): sort/bin tokens within G batch groups so the
    # routing data movement stays local to the data shards.  0 = one global
    # group (cross-shard sort; the naive baseline).  16 aligns with the
    # production data axis.
    n_groups: int = 0
    # pad the expert axis (dead experts are never routed to) so it divides
    # the model mesh axis and shard_mode='expert' applies (e.g. 60 -> 64)
    pad_experts_to: int = 0

    @property
    def effective_experts(self) -> int:
        return max(self.num_experts, self.pad_experts_to)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) block configuration."""

    state_dim: int = 128
    conv_width: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A single architecture.

    ``family`` selects the top-level model builder:
      dense | moe | ssm | hybrid | encdec | vlm
    (audio enc-dec uses family='encdec' with frontend='audio_frames';
    VLM uses family='vlm' with frontend='vision_patches').
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0  # 0 -> d_model // num_heads
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-5
    act: str = "silu"
    glu: bool = True  # gated MLP (SwiGLU / GeGLU)
    rope_theta: float = 10_000.0
    rotary_pct: float = 1.0
    rope_mode: str = "standard"  # standard | mrope | none
    qk_norm: bool = False
    qkv_bias: bool = False
    mlp_bias: bool = False
    tie_embeddings: bool = False

    moe: Optional[MoEConfig] = None
    moe_every: int = 1  # layer i is MoE iff moe is set and i % moe_every == 0
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2-style): one *shared* attention block invoked every N
    # backbone layers, with a per-site LoRA delta of this rank (0 = plain share)
    hybrid_attn_every: int = 0
    hybrid_lora_rank: int = 0

    # encoder/decoder split (family == 'encdec'); num_layers is the total.
    encoder_layers: int = 0
    decoder_layers: int = 0

    # modality frontend stub: none | vision_patches | audio_frames
    frontend: str = "none"
    frontend_tokens: int = 0  # patches / frames prepended per example
    frontend_dim: int = 0  # raw embedding dim supplied by the (stub) frontend

    max_seq_len: int = 32_768
    dtype: str = "bfloat16"
    vocab_pad_multiple: int = 2048

    # runtime knobs (overridable per run)
    remat: str = "dots"  # none | dots | full
    scan_layers: bool = True
    # flash: the flash kernel where attention.attend sees it applies (full
    # self-attention on one TPU), einsum elsewhere | einsum (GSPMD path) |
    # blocked | hd_sharded
    attention_impl: str = "flash"
    attn_scores_bf16: bool = False  # halve attention-score traffic (flagged numerics)

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.family not in ("dense", "moe", "ssm", "hybrid", "encdec", "vlm"):
            raise ValueError(f"unknown family {self.family!r}")
        if self.family == "moe" and self.moe is None:
            raise ValueError("family='moe' requires moe config")
        if self.family in ("ssm", "hybrid") and self.ssm is None:
            raise ValueError(f"family={self.family!r} requires ssm config")
        if self.family == "encdec" and not (self.encoder_layers and self.decoder_layers):
            raise ValueError("encdec requires encoder_layers and decoder_layers")

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // max(self.num_heads, 1)

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def uses_attention(self) -> bool:
        return self.family != "ssm" or self.hybrid_attn_every > 0

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch has a linear-cost sequence-mixing path (SSM/hybrid)."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (unpadded vocab), for 6ND roofline math."""
        d, hd = self.d_model, self.resolved_head_dim
        nq, nkv = self.num_heads, self.num_kv_heads
        v = self.vocab_size

        def attn_params() -> int:
            p = d * (nq * hd) + 2 * d * (nkv * hd) + (nq * hd) * d
            if self.qkv_bias:
                p += (nq + 2 * nkv) * hd
            if self.qk_norm:
                p += 2 * hd
            return p

        def dense_mlp_params(dff: int) -> int:
            mult = 3 if self.glu else 2
            p = mult * d * dff
            if self.mlp_bias:
                p += (mult - 1) * dff + d
            return p

        def ssm_params() -> int:
            assert self.ssm is not None
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            conv_dim = d_in + 2 * s.n_groups * s.state_dim
            p = d * (2 * d_in + 2 * s.n_groups * s.state_dim + nheads)  # in_proj
            p += conv_dim * s.conv_width  # depthwise conv
            p += nheads * 3  # A_log, D, dt_bias
            p += d_in  # gate norm
            p += d_in * d  # out_proj
            return p

        norms = 2 * d  # per layer (pre-attn + pre-mlp), rms weights only
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d  # lm head

        if self.family in ("dense", "vlm"):
            per_layer = attn_params() + dense_mlp_params(self.d_ff) + norms
            total += self.num_layers * per_layer
        elif self.family == "moe":
            assert self.moe is not None
            m = self.moe
            expert = dense_mlp_params(m.expert_d_ff)
            shared = dense_mlp_params(m.shared_d_ff) if m.num_shared_experts else 0
            router = d * m.num_experts
            per_layer = attn_params() + m.num_experts * expert + shared + router + norms
            total += self.num_layers * per_layer
        elif self.family == "ssm":
            total += self.num_layers * (ssm_params() + d)
        elif self.family == "hybrid":
            backbone = self.num_layers * (ssm_params() + d)
            shared_block = attn_params() + dense_mlp_params(self.d_ff) + norms
            n_sites = self.num_layers // max(self.hybrid_attn_every, 1)
            lora = 0
            if self.hybrid_lora_rank:
                r = self.hybrid_lora_rank
                lora = n_sites * 3 * (2 * d * r)  # q,k,v lora pairs per site
            total += backbone + shared_block + lora
        elif self.family == "encdec":
            enc_layer = attn_params() + dense_mlp_params(self.d_ff) + norms
            dec_layer = 2 * attn_params() + dense_mlp_params(self.d_ff) + 3 * d
            total += self.encoder_layers * enc_layer + self.decoder_layers * dec_layer
        if self.frontend != "none" and self.frontend_dim:
            total += self.frontend_dim * d  # frontend projection stub
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (== param_count except MoE top-k routing)."""
        if self.family != "moe":
            return self.param_count()
        assert self.moe is not None
        m = self.moe
        d = self.d_model

        def dense_mlp_params(dff: int) -> int:
            return (3 if self.glu else 2) * d * dff

        inactive_per_layer = (m.num_experts - m.top_k) * dense_mlp_params(m.expert_d_ff)
        return int(self.param_count() - self.num_layers * inactive_per_layer)


# ---------------------------------------------------------------------------
# Shapes (the assigned input-shape set)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Skip rules from the brief.

    * ``long_500k`` needs a sub-quadratic sequence path -> SSM/hybrid only.
    * decode shapes need a decoder (all archs in the pool have one).
    """
    if shape.name == "long_500k" and not model.sub_quadratic:
        return False, "long_500k skipped: pure full-attention arch (no sub-quadratic path)"
    return True, ""


# ---------------------------------------------------------------------------
# Mesh / parallelism
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh. ``pod`` is the cross-pod (DCN) axis; data/model are ICI."""

    data: int = 16
    model: int = 16
    pod: int = 1

    @property
    def num_devices(self) -> int:
        return self.pod * self.data * self.model

    @property
    def axis_names(self) -> tuple[str, ...]:
        return ("pod", "data", "model") if self.pod > 1 else ("data", "model")

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.pod, self.data, self.model) if self.pod > 1 else (self.data, self.model)

    @property
    def dp_axes(self) -> tuple[str, ...]:
        """Axes used for batch (data) parallelism."""
        return ("pod", "data") if self.pod > 1 else ("data",)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Sharding strategy knobs (resolved against a MeshConfig per arch)."""

    zero1: bool = True  # shard optimizer state over the data axes
    weights_2d: bool = False  # also shard weight d_model dim over 'data' (ZeRO-3-ish)
    seq_shard_prefill: bool = False  # context parallelism for long prefill
    grad_compression: str = "none"  # none | int8
    hierarchical_allreduce: bool = True  # pod-aware reduce for multi-pod
    num_microbatches: int = 1
    pipeline_stages: int = 1  # >1 enables the optional GPipe axis (tests only)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    optimizer: str = "adamw"  # adamw | adafactor | sgd
    z_loss_coef: float = 1e-4
    seed: int = 0
    checkpoint_every: int = 100
    keep_checkpoints: int = 3


# ---------------------------------------------------------------------------
# Architecture registry
# ---------------------------------------------------------------------------

ARCH_REGISTRY: dict[str, ModelConfig] = {}


def register_arch(cfg: ModelConfig) -> ModelConfig:
    if cfg.name in ARCH_REGISTRY:
        raise ValueError(f"duplicate arch {cfg.name!r}")
    ARCH_REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ModelConfig:
    # importing repro.configs populates the registry lazily
    if not ARCH_REGISTRY:
        import repro.configs  # noqa: F401
    if name not in ARCH_REGISTRY:
        import repro.configs  # noqa: F401
    try:
        return ARCH_REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCH_REGISTRY)}") from None


def list_archs() -> list[str]:
    if not ARCH_REGISTRY:
        import repro.configs  # noqa: F401
    return sorted(ARCH_REGISTRY)


def scale_down(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Produce a reduced config of the same family for CPU smoke tests."""
    small = dict(
        num_layers=min(cfg.num_layers, 2),
        d_model=128,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads < cfg.num_heads else 4,
        head_dim=32,
        d_ff=256,
        vocab_size=512,
        vocab_pad_multiple=64,
        max_seq_len=512,
        dtype="float32",
        scan_layers=cfg.scan_layers,
        frontend_tokens=min(cfg.frontend_tokens, 8) if cfg.frontend_tokens else 0,
        frontend_dim=64 if cfg.frontend_dim else 0,
    )
    if cfg.moe is not None:
        small["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=min(cfg.moe.num_experts, 8),
            top_k=min(cfg.moe.top_k, 2),
            expert_d_ff=64,
            shared_d_ff=64 if cfg.moe.num_shared_experts else 0,
            # ample capacity: smoke tests check decode == full forward, which
            # only holds exactly when no token is capacity-dropped
            capacity_factor=8.0,
        )
    if cfg.ssm is not None:
        small["ssm"] = dataclasses.replace(
            cfg.ssm, state_dim=16, head_dim=16, chunk_size=64
        )
    if cfg.family == "encdec":
        small["encoder_layers"] = min(cfg.encoder_layers, 2)
        small["decoder_layers"] = min(cfg.decoder_layers, 2)
        small["num_layers"] = small["encoder_layers"] + small["decoder_layers"]
    if cfg.hybrid_attn_every:
        small["hybrid_attn_every"] = 2
    small.update(overrides)
    small["name"] = cfg.name + "-smoke"
    return dataclasses.replace(cfg, **small)
