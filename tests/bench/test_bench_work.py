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
