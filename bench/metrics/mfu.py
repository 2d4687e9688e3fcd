"""Model FLOP utilization of the train step, in % of the chips' bf16 peak.

Model FLOPs per token (``bench/work.py``: 6 x matmul parameters plus causal
attention, no recomputation) times the traced run's tokens per second,
over chips times the peak of ``bench/peaks.json``.
"""
from bench import work


def read(run):
    tps = run.end_to_end.get("tokens_per_s")
    if not tps or run.peaks is None:
        return None
    flops = work.train_flops_per_token(run.data["model"], run.data["seq_len"])
    return 100.0 * flops * tps / (run.cell.chips
                                  * run.peaks["bf16_flops_per_s"])
