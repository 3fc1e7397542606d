"""Config system: model configs, input-shape configs, and the arch registry.

Every assigned architecture is a ``ModelConfig`` built from a repeating
*period* of layer kinds (e.g. gemma3 = 5 local + 1 global attention layers),
which is what lets the layer stack lower as a ``lax.scan`` over periods and
what gives Sentinel its migration-interval block structure.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence


# Layer kinds that can appear in a period.
ATTN = "attn"            # full causal attention + MLP
LOCAL = "local"          # sliding-window attention + MLP
MLA = "mla"              # multi-head latent attention (deepseek) + MoE/MLP
MAMBA = "mamba"          # mamba2 SSD block
SHARED_ATTN = "shared_attn"  # zamba2 shared transformer block (one weight copy)
MLSTM = "mlstm"          # xLSTM matrix-LSTM block
SLSTM = "slstm"          # xLSTM scalar-LSTM block
LSTM = "lstm"            # classic LSTM (paper's own PTB model)


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0           # routed experts
    experts_per_token: int = 0     # top-k
    num_shared_experts: int = 0
    d_ff: int = 0                  # per-expert hidden
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    group_size: int = 512          # tokens per dispatch group (GShard-style);
                                   # dispatch memory ~ T * group_size * k * factor
    norm_topk_prob: bool = True    # renormalise the k chosen gate weights
                                   # (False: the softmax over all experts,
                                   # as DeepSeek-V2 publishes it)
    dropless: bool = False         # every routed token reaches its expert
                                   # (the held-expert layer, moe.held_moe)
                                   # instead of the capacity dispatch
    first_expert: int = 0          # this chip holds experts
    num_held: int = 0              # [first_expert, first_expert + num_held);
                                   # 0: all of them (no expert parallelism)

    @property
    def held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.num_held or self.num_experts


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (``rope_scaling`` of type ``yarn``, as
    DeepSeek-V2 publishes it): the rotary frequencies blend interpolated and
    extrapolated ones between the correction dims of ``beta_fast`` and
    ``beta_slow``, and the attention scale grows by ``mscale_all_dim``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 64           # N
    head_dim: int = 64            # P
    num_heads: int = 0            # filled from d_inner // head_dim if 0
    expand: int = 2               # d_inner = expand * d_model
    conv_kernel: int = 4
    chunk: int = 256              # SSD chunk length


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | hybrid | ssm | audio | vlm | lstm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // num_heads

    # layer period (repeats num_layers // len(period) times)
    period: Sequence[str] = (ATTN,)
    prologue: Sequence[str] = ()  # unstacked leading layers (deepseek dense layer 0)
    prologue_d_ff: int = 0

    # attention details
    sliding_window: int = 0
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_theta_global: float = 0.0   # gemma3 global layers use a different theta
    prefix_lm: bool = False          # paligemma: bidirectional prefix

    # MLA (deepseek)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    kv_norm: bool = False         # RMSNorm on the latent (kv_a_layernorm)
    rope_yarn: Optional[YarnConfig] = None   # YaRN scaling of the rope dims

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None

    # modality stubs
    num_codebooks: int = 0        # musicgen: 4 EnCodec codebooks
    num_prefix_tokens: int = 0    # paligemma: SigLIP patch embeddings (stub)

    act: str = "silu"             # silu | gelu
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    scale_embed: bool = False     # gemma family scales embeddings by sqrt(d)
    vocab_pad_to: int = 256       # pad embedding table for even TP sharding
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        assert self.num_layers >= len(self.prologue)
        n = self.num_layers - len(self.prologue)
        assert n % len(self.period) == 0, (
            f"{self.name}: {n} layers not divisible by period {len(self.period)}")

    # ------------------------------------------------------------------
    @property
    def period_len(self) -> int:
        return len(self.period)

    @property
    def num_periods(self) -> int:
        return (self.num_layers - len(self.prologue)) // len(self.period)

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p

    @property
    def has_attention(self) -> bool:
        kinds = set(self.period) | set(self.prologue)
        return bool(kinds & {ATTN, LOCAL, MLA, SHARED_ATTN})

    @property
    def subquadratic(self) -> bool:
        """Eligible for the long_500k shape (SSM/hybrid/linear or local-dominant)."""
        kinds = set(self.period)
        if kinds & {MAMBA, MLSTM, SLSTM, LSTM}:
            return True
        # local-attention-dominant archs (gemma2/3) decode in O(window) for
        # local layers; treated as sub-quadratic per DESIGN.md §5.
        return LOCAL in kinds

    def reduced(self) -> "ModelConfig":
        """Tiny same-family variant for CPU smoke tests."""
        kw = dict(
            name=self.name + "-smoke",
            num_layers=len(self.prologue) + 2 * self.period_len,
            d_model=64,
            num_heads=4,
            num_kv_heads=max(1, min(self.num_kv_heads, 2)),
            d_ff=128,
            vocab_size=512,
            head_dim=16,
            prologue_d_ff=128 if self.prologue else 0,
            sliding_window=min(self.sliding_window, 8) if self.sliding_window else 0,
            q_lora_rank=32 if self.q_lora_rank else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_dim=16 if self.qk_nope_dim else 0,
            qk_rope_dim=8 if self.qk_rope_dim else 0,
            v_head_dim=16 if self.v_head_dim else 0,
            # capacity_factor high enough to be dropless at toy scale so
            # prefill/decode parity holds exactly (capacity dropping is
            # batch-shape-dependent by construction)
            moe=dataclasses.replace(self.moe, num_experts=4, experts_per_token=2,
                                    d_ff=64, capacity_factor=4.0,
                                    first_expert=0, num_held=0)
            if self.moe else None,
            ssm=dataclasses.replace(self.ssm, state_dim=16, head_dim=8, num_heads=0,
                                    chunk=8) if self.ssm else None,
            num_prefix_tokens=4 if self.num_prefix_tokens else 0,
            dtype="float32",
        )
        return dataclasses.replace(self, **{k: v for k, v in kw.items()})


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list:
    if not _REGISTRY:
        _load_all()
    return sorted(_REGISTRY)


def _load_all():
    # import the per-arch modules for their registration side effect
    from repro.configs import (  # noqa: F401
        smollm_360m, gemma3_12b, internlm2_1p8b, gemma2_2b,
        granite_moe_3b, deepseek_v2_lite, zamba2_7b, xlstm_1p3b,
        musicgen_medium, paligemma_3b, lstm_ptb,
    )


def cells(include_skips: bool = False):
    """All (arch, shape) dry-run cells; skips per DESIGN.md §5."""
    out = []
    for arch in list_archs():
        cfg = get_config(arch)
        if arch == "lstm-ptb":
            continue  # paper's own model: not part of the 40 assigned cells
        for sname, shape in SHAPES.items():
            skip = sname == "long_500k" and not cfg.subquadratic
            if skip and not include_skips:
                continue
            out.append((arch, sname, skip))
    return out
