"""The work an algorithm needs, counted from shapes.

These count what the mathematics requires, not what an implementation
happens to execute: recomputation (remat), masked-out attention scores,
padding and host copies are not counted, so a faster implementation of the
same step cannot change them.
"""
from __future__ import annotations


def dense_lm_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul per token: the attention
    projections and the (gated) MLP of every layer, and the output head.
    The input embedding is a lookup, not a matmul."""
    d, L = cfg["d_model"], cfg["n_layers"]
    attn = d * cfg["n_heads"] * cfg["d_head"] * 2            # q, o
    attn += d * cfg["n_kv_heads"] * cfg["d_head"] * 2        # k, v
    mlp = (3 if cfg["gated_mlp"] else 2) * d * cfg["d_ff"]
    return L * (attn + mlp) + d * cfg["vocab_size"]


def causal_attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward score and value products of causal attention,
    per token, averaged over the positions of a sequence of ``seq``: the
    query at position i attends to i + 1 keys, 2 FLOPs per multiply-add,
    QK^T and AV each, backward twice the forward."""
    per_key = 2 * 2 * cfg["n_heads"] * cfg["d_head"]          # fwd QK + AV
    mean_keys = (seq + 1) / 2
    return 3 * cfg["n_layers"] * per_key * mean_keys


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one training token (forward + backward):
    6 x matmul parameters plus causal attention."""
    return (6.0 * dense_lm_matmul_params(cfg)
            + causal_attention_flops_per_token(cfg, seq))


def ps_apply_bytes(n_entries: int, n_distinct: int, n_cols: int,
                   itemsize: int) -> int:
    """HBM bytes one scatter-add of ``n_entries`` delta rows into
    ``n_distinct`` distinct table rows needs: each distinct row read and
    written once, each delta row read once."""
    return (2 * n_distinct + n_entries) * n_cols * itemsize
