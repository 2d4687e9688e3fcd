"""Each per-layer metric's reader, on a run whose numbers are set by hand:
the value it derives, and nothing (never 0) when it finds nothing."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace  # noqa: E402
from bench import run as R  # noqa: E402

V5E = R.peaks_of("TPU v5 lite")


def fake_run(chips=1, **data):
    cell = R.Cell("hand", chips, {}, {}, {}, [], [])
    run = R.Run(cell, seed=0, seconds=10.0, trace=True, peaks=V5E)
    run.window_s = 10.0
    run.data.update(data)
    return run


def test_mfu_of_a_train_step():
    run = fake_run(model={"d_model": 2, "n_layers": 1, "n_heads": 1,
                          "n_kv_heads": 1, "d_head": 2, "d_ff": 4,
                          "gated_mlp": True, "vocab_size": 8},
                   seq_len=1)
    run.end_to_end["tokens_per_s"] = 1e9
    # matmul parameters: q, o 2x2, k, v 2x2, gated MLP 3x2x4, head 2x8 = 56;
    # causal attention at seq 1: 3 x 4 x (1 x 2) x 1 = 24
    want = 100 * (6 * 56 + 24) * 1e9 / 197e12
    assert R.reader("mfu")(run) == pytest.approx(want)
    assert R.reader("mfu")(fake_run()) is None


def test_shares_of_runtime_spans():
    run = fake_run(apply_ns=3e9, block_clock_ns=2e9,
                   runtime_window_ns=10e9, active_shards=2, workers=4)
    assert R.reader("shard_apply_share")(run) == pytest.approx(15.0)
    assert R.reader("worker_blocked_share")(run) == pytest.approx(5.0)
    assert R.reader("shard_apply_share")(fake_run()) is None
    assert R.reader("worker_blocked_share")(fake_run()) is None


def test_ps_apply_roofline():
    # one batch of 4 entries on 2 distinct rows of 128 f32: 1,024 x 8 B
    applies = [(4, 2, 128, 4)]
    need = (2 * 2 + 4) * 128 * 4
    ops = [devtrace.Op("scatter_add_pallas.1", 0, 1000),
           devtrace.Op("copy.1", 1000, 5000)]
    tr = devtrace.Trace({"0": ops}, [])
    run = fake_run(applies_in_window=applies, trace=tr,
                   trace_window=(0, 10_000))
    assert R.reader("ps_apply_roofline")(run) == pytest.approx(
        100 * need / 819e9 / 1e-6)
    assert R.reader("ps_apply_roofline")(fake_run()) is None


def test_device_readers():
    tr = devtrace.Trace({"0": [devtrace.Op("all-reduce.1", 0, 4_000_000),
                               devtrace.Op("fusion.1", 3_000_000,
                                           8_000_000)],
                         "1": [devtrace.Op("all-reduce.1", 0, 2_000_000)]},
                        [])
    run = fake_run(chips=2, steps=2)
    run.device_summary = devtrace.summarize(tr, (0, 10_000_000))
    # exposed: 3 ms on chip 0, 2 ms on chip 1: a mean of 2.5 ms over 2
    # steps; busy 8 and 2 of 10 ms
    assert R.reader("allreduce_exposed_ms")(run) == pytest.approx(1.25)
    assert R.reader("device_idle_share.train")(run) == pytest.approx(50.0)
    assert R.reader("device_idle_share.ps")(run) == pytest.approx(50.0)
    one = fake_run(steps=2)
    one.device_summary = devtrace.summarize(tr, (0, 10_000_000), ["0"])
    assert R.reader("allreduce_exposed_ms")(one) is None
    for name in ("allreduce_exposed_ms", "device_idle_share.train"):
        assert R.reader(name)(fake_run()) is None
