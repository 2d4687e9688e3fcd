"""The work an algorithm needs, counted from shapes.

These count what the mathematics requires, not what an implementation
happens to execute: recomputation (remat), masked-out attention scores,
padding and host copies are not counted, so a faster implementation of the
same step cannot change them.

A train configuration is its file's dict (``bench/configs/``): the
decoder's widths at the top level, and the sub-configs as the file states
them.  ``mla`` (latent attention) gives ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim``.  ``moe``
gives ``n_experts`` (the router's width, as published), ``top_k``,
``d_expert``, ``n_shared_experts`` and ``d_shared`` (the shared experts'
hidden width, all of them together), ``first_dense_layers`` and their
``d_ff_dense``, and ``experts_held``: the routed experts this chip holds
of each layer, where the layer is divided over several chips (all of them
where the key is absent).
"""
from __future__ import annotations


def _decoder_only(cfg: dict) -> None:
    """The counts below are of a decoder whose every layer is full causal
    attention and a feed-forward block; refuse what they would miscount."""
    if (cfg.get("recurrent") or cfg.get("attn_kind", "full") != "full"
            or tuple(cfg.get("layer_pattern", ("attn",))) != ("attn",)):
        raise ValueError("bench/work.py counts decoders of full causal "
                         "attention layers only")


def attention_params(cfg: dict) -> int:
    """Matmul parameters of one layer's attention: q, k, v and o, or with
    latent attention q, the kv down-projection ``w_dkv`` (to the latent and
    the shared rotary key), the up-projections ``w_uk`` and ``w_uv``, and
    o."""
    d, h = cfg["d_model"], cfg["n_heads"]
    mla = cfg.get("mla")
    if mla is None:
        return (d * h * cfg["d_head"] * 2                      # q, o
                + d * cfg["n_kv_heads"] * cfg["d_head"] * 2)   # k, v
    r, nope = mla["kv_lora_rank"], mla["qk_nope_head_dim"]
    rope, dv = mla["qk_rope_head_dim"], mla["v_head_dim"]
    return (d * h * (nope + rope)                              # q
            + d * (r + rope)                                   # w_dkv
            + r * h * nope + r * h * dv                        # w_uk, w_uv
            + h * dv * d)                                      # o


def dense_lm_matmul_params(cfg: dict) -> float:
    """Parameters that take part in a matmul per token: the attention
    projections and the feed-forward block of every layer, and the output
    head once.  The input embedding is a lookup, not a matmul.

    With experts (``moe``) the leading ``first_dense_layers`` have a dense
    block of ``d_ff_dense``; every other layer has the router (all
    ``n_experts`` outputs), the shared experts, and the routed experts a
    token meets here: ``top_k`` of ``n_experts``, of which this chip holds
    ``experts_held``, so ``top_k * experts_held / n_experts`` of them on
    average."""
    _decoder_only(cfg)
    d, L = cfg["d_model"], cfg["n_layers"]
    ffn = 3 if cfg["gated_mlp"] else 2
    moe = cfg.get("moe")
    total = L * attention_params(cfg) + d * cfg["vocab_size"]
    if moe is None:
        return total + L * ffn * d * cfg["d_ff"]
    dense = moe.get("first_dense_layers", 0)
    held = moe.get("experts_held", moe["n_experts"])
    expert_layer = d * moe["n_experts"]                         # router
    if moe.get("n_shared_experts", 0):
        expert_layer += ffn * d * moe["d_shared"]
    expert_layer += (moe["top_k"] * held * ffn * d * moe["d_expert"]
                     / moe["n_experts"])
    return (total + dense * ffn * d * moe["d_ff_dense"]
            + (L - dense) * expert_layer)


def causal_attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward score and value products of causal attention,
    per token, averaged over the positions of a sequence of ``seq``: the
    query at position i attends to i + 1 keys, 2 FLOPs per multiply-add,
    QK^T over the query/key head width and AV over the value head width,
    backward twice the forward."""
    _decoder_only(cfg)
    mla = cfg.get("mla")
    if mla is None:
        d_qk = d_v = cfg["d_head"]
    else:
        d_qk = mla["qk_nope_head_dim"] + mla["qk_rope_head_dim"]
        d_v = mla["v_head_dim"]
    per_key = 2 * cfg["n_heads"] * (d_qk + d_v)                 # fwd QK + AV
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
