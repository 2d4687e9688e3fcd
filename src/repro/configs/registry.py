"""Architecture registry: ``--arch <id>`` → ModelConfig.

Besides the 10 full assigned configs, every architecture exposes a REDUCED
smoke variant (≤2 layers, d_model ≤ 512, ≤4 experts) used by the per-arch CPU
smoke tests.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import (FrontendConfig, MLAConfig, ModelConfig,
                                MoEConfig, RecurrentConfig)

from repro.configs.olmoe_1b_7b import CONFIG as _olmoe
from repro.configs.olmo_1b import CONFIG as _olmo
from repro.configs.pixtral_12b import CONFIG as _pixtral
from repro.configs.qwen3_8b import CONFIG as _qwen3
from repro.configs.gemma2_9b import CONFIG as _gemma2_9b
from repro.configs.gemma2_2b import CONFIG as _gemma2_2b
from repro.configs.recurrentgemma_9b import CONFIG as _recurrentgemma
from repro.configs.musicgen_medium import CONFIG as _musicgen
from repro.configs.deepseek_v2_lite_16b import CONFIG as _deepseek
from repro.configs.mamba2_130m import CONFIG as _mamba2

ARCHS = {
    c.name: c
    for c in [
        _olmoe, _olmo, _pixtral, _qwen3, _gemma2_9b, _gemma2_2b,
        _recurrentgemma, _musicgen, _deepseek, _mamba2,
    ]
}

# ---------------------------------------------------------------------------
# Beyond-paper performance variants — NOT part of the assigned 10;
# selectable by name for A/B runs.
# ---------------------------------------------------------------------------
ARCHS["mamba2-130m-sp"] = dataclasses.replace(
    _mamba2, name="mamba2-130m-sp", tp_strategy="seq_ssm")
ARCHS["pixtral-12b-cg"] = dataclasses.replace(
    _pixtral, name="pixtral-12b-cg", compress_gathers=True)


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def reduced_config(name: str) -> ModelConfig:
    """A tiny same-family variant for CPU smoke tests (≤2 layers, d≤512, ≤4e)."""
    cfg = get_config(name)
    kw = dict(
        name=cfg.name + "-smoke",
        vocab_size=512,
        d_model=128,
        window=32,
        long_context_window=32,
        tp_strategy=cfg.tp_strategy,
    )
    if cfg.has_attention:
        kw.update(n_heads=4, n_kv_heads=min(cfg.n_kv_heads, 4) or 4, d_head=32)
    if cfg.layer_pattern == ("attn",):
        kw["n_layers"] = 2
    else:
        kw["n_layers"] = len(cfg.layer_pattern)      # one full pattern period
    if cfg.d_ff:
        kw["d_ff"] = 256
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=2, d_expert=64,
            d_shared=64 if cfg.moe.n_shared_experts else 0,
            d_ff_dense=128 if cfg.moe.first_dense_layers else 0,
        )
        if cfg.moe.first_dense_layers:
            kw["n_layers"] = 2                       # 1 dense + 1 moe layer
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=32,
                              qk_rope_head_dim=16, v_head_dim=32)
        kw["d_head"] = 48
    if cfg.recurrent is not None:
        if cfg.recurrent.kind == "rglru":
            kw["recurrent"] = dataclasses.replace(cfg.recurrent, width=128)
        else:
            kw["recurrent"] = dataclasses.replace(
                cfg.recurrent, width=128, head_dim=32, d_state=16, chunk_size=16)
    if cfg.frontend is not None:
        kw["frontend"] = FrontendConfig(kind=cfg.frontend.kind, n_embeds=8,
                                        embed_dim=128)
    return dataclasses.replace(cfg, **kw)
