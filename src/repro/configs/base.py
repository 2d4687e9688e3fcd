"""Configuration dataclasses for the model zoo and the distributed runtime.

Every assigned architecture is expressed as a :class:`ModelConfig`.  Configs
are plain frozen dataclasses so they hash, print, and diff cleanly; the
registry in :mod:`repro.configs.registry` maps ``--arch`` ids onto them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts feed-forward configuration."""

    n_experts: int
    top_k: int
    d_expert: int                     # hidden width of each routed expert
    n_shared_experts: int = 0         # DeepSeek-style always-on experts
    d_shared: int = 0                 # hidden width of the shared expert block
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01     # load-balance loss coefficient
    first_dense_layers: int = 0       # leading layers that use a dense FFN
    d_ff_dense: int = 0               # hidden width of those dense layers


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2)."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class RecurrentConfig:
    """Recurrent mixer configuration (RG-LRU or Mamba-2 SSD)."""

    kind: str = "rglru"               # "rglru" | "mamba2"
    width: int = 0                    # recurrence width (d_inner)
    conv_width: int = 4
    # mamba2-only:
    d_state: int = 128
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256
    # rglru-only:
    block_width: int = 0              # rglru gate block-diagonal width (0 = dense)


@dataclass(frozen=True)
class FrontendConfig:
    """Stubbed modality frontend (VLM patches / audio conditioning).

    Per the brief, the conv/ViT encoder itself is NOT implemented; the
    frontend contributes precomputed embeddings via ``input_specs``.
    """

    kind: str                         # "vision" | "audio"
    n_embeds: int                     # patches (vision) / conditioning frames (audio)
    embed_dim: int                    # dimension of provided embeddings


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                    # dense|moe|vlm|audio|hybrid|ssm
    source: str                       # citation for the assignment table
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # --- attention flavour ---------------------------------------------------
    attn_kind: str = "full"           # "full" | "swa" | "alternating" (local/global)
    # int8-compress the sequence-parallel all-gathers (lossy ~0.4% activation
    # error; halves the dominant collective volume — EXPERIMENTS §Perf pair 2)
    compress_gathers: bool = False
    window: int = 4096                # sliding-window size where applicable
    qk_norm: bool = False
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 10_000.0

    # --- block flavour -------------------------------------------------------
    norm_kind: str = "rmsnorm"        # "rmsnorm" | "gemma_rmsnorm" | "layernorm" | "nonparam_ln"
    post_norm: bool = False           # gemma2-style post-sublayer norms
    act: str = "silu"                 # "silu" | "gelu"
    gated_mlp: bool = True            # SwiGLU/GeGLU vs plain MLP
    tie_embeddings: bool = False
    layer_pattern: Tuple[str, ...] = ("attn",)   # cycled over layers

    # --- optional subsystems -------------------------------------------------
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    recurrent: Optional[RecurrentConfig] = None
    frontend: Optional[FrontendConfig] = None

    # --- distribution --------------------------------------------------------
    tp_strategy: str = "head"         # "head" | "seq" | "replicated"
    # long-context mode: attention archs fall back to sliding-window caches so
    # that a 500k-token decode has bounded memory.
    long_context_window: int = 4096

    # --- numerics ------------------------------------------------------------
    dtype: str = "bfloat16"           # activation/param compute dtype
    param_dtype: str = "float32"      # master/optimizer dtype

    # -------------------------------------------------------------------------
    def __post_init__(self):
        if self.d_head == 0 and self.n_heads:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)

    # Padded vocab so the output head shards evenly over the model axis.
    def padded_vocab(self, tp: int) -> int:
        v = self.vocab_size
        return ((v + tp - 1) // tp) * tp

    @property
    def has_attention(self) -> bool:
        return "attn" in self.layer_pattern

    def layer_kinds(self) -> Tuple[str, ...]:
        """Expanded per-layer kind list of length n_layers."""
        pat = self.layer_pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))


# ---------------------------------------------------------------------------
# Input shapes (prefill and serve steps)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str                         # "train" | "prefill" | "decode"


# ---------------------------------------------------------------------------
# Training / runtime config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ConsistencySpec:
    """User-facing consistency selection; mirrors the paper's policies."""

    model: str = "bsp"                # bsp|ssp|cap|essp|vap|cvap|elastic
    staleness: int = 0                # s  (ssp/cap/essp/cvap)
    value_bound: float = 0.0          # v_thr (vap/cvap) / norm B (elastic)
    strong: bool = False              # strong VAP variant (simulator only)


@dataclass(frozen=True)
class TrainConfig:
    arch: str = "olmo-1b"
    steps: int = 100
    lr: float = 3e-4
    warmup_steps: int = 10
    weight_decay: float = 0.0
    optimizer: str = "adam"           # "sgd" | "momentum" | "adam"
    seed: int = 0
    consistency: ConsistencySpec = field(default_factory=ConsistencySpec)
    remat: bool = True
    log_every: int = 10
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    # beyond the paper: a bf16 sync all-reduce and a narrower state dtype
    quantize_sync: bool = False       # bf16 delta all-reduce (error feedback)
    state_dtype: str = "float32"      # delta + Adam moments storage dtype


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)
