"""The PS cell's check, driven through the whole harness at a CPU size:
a sound run is correct, an answer altered where the shard applies it
turns ``correct`` false, and so does the bfloat16 control."""
import numpy as np

from benchtiny import failed_checks, run_tiny, tiny_cell

CELL = "lda-nytimes.ssp2"


def test_sound_run_is_correct():
    out = run_tiny(CELL, seconds=0.5)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"row_updates_per_s", "clock_ms_p90",
                                   "setup_s"}
    assert out["checks"]["table_mismatches"] == {"value": 0.0, "limit": 0}


def test_answer_altered_where_the_shard_applies_it(monkeypatch):
    from repro.kernels.ps_apply import ops
    apply = ops.scatter_add_inplace

    def altered(dense, rows, delta):
        delta = delta.copy()
        delta[0, 0] += 1.0
        return apply(dense, rows, delta)

    monkeypatch.setattr(ops, "scatter_add_inplace", altered)
    out = run_tiny(CELL, seconds=0.5)
    assert not out["correct"]
    assert "table_mismatches" in failed_checks(out)


def test_bfloat16_control_fails_the_check():
    from bench.control import ps_control
    rec = ps_control(tiny_cell(CELL), seed=7, clocks=20)
    assert rec["bf16"]["correct"] is False, rec
    assert rec["bf16"]["checks"]["table_mismatches"]["value"] > 0


def test_bfloat16_control_by_hand():
    """Counts above 256 are not all representable in bfloat16."""
    from bench.reference import lda_counts
    import ml_dtypes
    x0 = np.full((1, 2), 255.0, np.float32)
    moves = [(np.array([0, 0]), np.array([1, 1]), np.array([0, 0]))]
    exact = lda_counts.final_table(x0, moves)
    assert exact.tolist() == [[257.0, 253.0]]
    low = lda_counts.final_table(x0, moves, dtype=ml_dtypes.bfloat16)
    assert lda_counts.mismatches(low, exact) == 1
