"""The benchmark's counts of needed work, against counts made by hand."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import work  # noqa: E402


def olmo_cut():
    with open(os.path.join(ROOT, "bench", "configs", "olmo-1b-4l.json")) as f:
        return json.load(f)


def test_olmo_cut_matmul_parameters():
    # per layer: q, k, v, o 2048 x 2048 each; gated MLP 3 x 2048 x 8192;
    # output head (tied embedding) 2048 x 50,304
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert per_layer == 67_108_864
    assert work.dense_lm_matmul_params(olmo_cut()) == (
        4 * per_layer + 2048 * 50_304) == 371_458_048


def test_olmo_cut_train_flops_per_token():
    # causal attention at seq 2048: a query attends to 1,024.5 keys on
    # average; QK^T and AV are 2 x 16 x 128 multiply-adds per key each,
    # 2 FLOPs per multiply-add, backward twice the forward, 4 layers
    attn = 3 * 4 * (2 * 2 * 16 * 128) * 1024.5
    assert attn == 100_712_448
    assert work.train_flops_per_token(olmo_cut(), 2048) == (
        6 * 371_458_048 + attn)


def test_causal_mean_keys_by_hand():
    cfg = {"n_layers": 1, "n_heads": 1, "d_head": 1}
    # seq 2: the two positions attend to 1 and 2 keys; 4 FLOPs per key
    # forward, x3 with the backward
    assert work.causal_attention_flops_per_token(cfg, 2) == 3 * 4 * 1.5


def test_ps_apply_bytes_by_hand():
    rows = np.array([3, 1, 3, 7, 1])
    # 3 distinct rows read and written, 5 delta rows read, 128 f32 each
    assert work.ps_apply_bytes(5, 3, 128, 4) == (2 * 3 + 5) * 128 * 4
    assert len(np.unique(rows)) == 3


@pytest.mark.parametrize("rows", [
    np.array([3, 1, 3, 7, 1]),
    np.arange(40) % 9,
    np.array([5]),
])
def test_ps_apply_bytes_same_for_numpy_and_kernel_paths(rows, monkeypatch):
    """The count comes from the batch, not from the path that applies
    it: the numpy path (REPRO_PALLAS=off) and the kernel (interpreted)
    log the same bytes and leave the same block."""
    from bench.ps_cell import counted
    from repro.kernels.ps_apply import ops as apply_ops
    rng = np.random.default_rng(0)
    delta = rng.integers(-3, 4, (rows.shape[0], 128)).astype(np.float32)
    blocks, needs = {}, {}
    for mode in ("off", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        log = []
        block = np.zeros((16, 128), np.float32)
        counted(apply_ops.scatter_add_inplace, log)(block, rows, delta)
        blocks[mode] = block
        needs[mode] = sum(work.ps_apply_bytes(*rec[1:]) for rec in log)
    assert needs["off"] == needs["interpret"] == work.ps_apply_bytes(
        rows.shape[0], len(np.unique(rows)), 128, 4)
    np.testing.assert_array_equal(blocks["off"], blocks["interpret"])


def deepseek_v2_lite_cut():
    """DeepSeek-V2-Lite at its published widths (huggingface.co/deepseek-ai/
    DeepSeek-V2-Lite config.json), cut to one chip of a deployment in which
    8 chips share each layer: the dense layer and 4 expert layers, 8 of
    each layer's 64 experts, an eighth of the 102,400-id vocabulary."""
    return {"n_layers": 5, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
            "d_head": 192, "d_ff": 1408, "vocab_size": 12_800,
            "gated_mlp": True,
            "mla": {"kv_lora_rank": 512, "qk_nope_head_dim": 128,
                    "qk_rope_head_dim": 64, "v_head_dim": 128},
            "moe": {"n_experts": 64, "top_k": 6, "d_expert": 1408,
                    "n_shared_experts": 2, "d_shared": 2816,
                    "first_dense_layers": 1, "d_ff_dense": 10944,
                    "experts_held": 8}}


def test_deepseek_cut_latent_attention_projections():
    # q 2048 x 16 x (128 + 64); w_dkv 2048 x (512 + 64); w_uk, w_uv
    # 512 x 16 x 128 each; o 16 x 128 x 2048
    by_hand = (2048 * 16 * 192 + 2048 * 576 + 2 * 512 * 16 * 128
               + 16 * 128 * 2048)
    assert by_hand == 13_762_560
    assert work.attention_params(deepseek_v2_lite_cut()) == by_hand


def test_deepseek_cut_matmul_parameters():
    cfg = deepseek_v2_lite_cut()
    attention = 5 * 13_762_560
    dense = 3 * 2048 * 10944                  # the leading dense layer
    # router 2048 x 64; shared experts 3 x 2048 x 2816; routed experts:
    # 6 of 64 a token, 8 held here, so 6 x 8 / 64 = 0.75 of one expert's
    # 3 x 2048 x 1408
    expert_layer = 2048 * 64 + 3 * 2048 * 2816 + 3 * 2048 * 1408 * 3 // 4
    assert expert_layer == 23_920_640
    head = 2048 * 12_800
    assert work.dense_lm_matmul_params(cfg) == (
        attention + dense + 4 * expert_layer + head) == 257_949_696
    # every expert held: 6 routed experts a token
    del cfg["moe"]["experts_held"]
    assert work.dense_lm_matmul_params(cfg) == (
        257_949_696 + 4 * 3 * 2048 * 1408 * (6 - 0.75))


def test_deepseek_cut_attention_flops_per_token():
    # a key costs 2 x 16 heads x (192 for QK^T + 128 for AV) forward; a
    # query at seq 4096 attends to 2,048.5 keys on average; backward twice
    # the forward; 5 layers
    attn = 3 * 5 * (2 * 16 * (192 + 128)) * 2048.5
    assert attn == 314_649_600
    cfg = deepseek_v2_lite_cut()
    assert work.causal_attention_flops_per_token(cfg, 4096) == attn
    # the cell's step: 8 x 4096 tokens, about 61.0 TFLOP
    step = work.train_flops_per_token(cfg, 4096) * 8 * 4096
    assert step == (6 * 257_949_696 + attn) * 8 * 4096
    assert round(step / 1e12, 1) == 61.0


@pytest.mark.parametrize("change", [{"recurrent": {"kind": "rglru"}},
                                    {"attn_kind": "swa"},
                                    {"layer_pattern": ["attn", "rec"]}])
def test_counts_refuse_what_they_would_miscount(change):
    cfg = dict(olmo_cut(), **change)
    with pytest.raises(ValueError, match="full causal attention"):
        work.train_flops_per_token(cfg, 2048)
