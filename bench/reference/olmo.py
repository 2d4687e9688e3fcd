"""Plain reference of the OLMo decoder LM: its initial weights and its loss.

Written from the published description of OLMo (arXiv:2402.00838), in
plain ``jax.numpy`` at float32 with every matmul at ``Precision.HIGHEST``
(or the float8 control, through ``training.einsum``).  It imports nothing
of the system under test; ``bench/reference/training.py`` trains it.

Model: token embedding (tied with the output head), then per layer a
non-parametric LayerNorm, causal multi-head attention with rotary position
embeddings on the two halves of each head, a residual add, a second
LayerNorm, a SwiGLU MLP ``(silu(h W_in) * (h W_gate)) W_out`` and a
residual add; a final LayerNorm, logits against the embedding, and the mean
next-token cross-entropy over every token of the batch.

Initial weights are drawn from the run's key by the recipe the configuration
states: one key per weight matrix, split in the order
``embed, w_gate, w_in, w_out, wk, wo, wq, wv``; the embedding is normal with
std 1/sqrt(d_model); every other matrix is a standard normal truncated to
[-3, 3] (``truncated_normal(-3, 3)``) times 1/sqrt(fan_in).  Block weights
are stacked over layers, one draw per stacked matrix; the block's
attention weights are named under ``mix``, its MLP's under ``ffn``.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference.training import einsum

# each block weight, stacked over layers, and its part of the block
BLOCK = {"w_gate": "ffn", "w_in": "ffn", "w_out": "ffn", "wk": "mix",
         "wo": "mix", "wq": "mix", "wv": "mix"}


def init_params(cfg: dict, key) -> Dict[str, jnp.ndarray]:
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    hq = cfg["n_heads"] * cfg["d_head"]
    hkv = cfg["n_kv_heads"] * cfg["d_head"]
    ff = cfg["d_ff"]
    k = jax.random.split(key, 8)
    f32 = jnp.float32

    def tn(kk, shape, fan_in):
        return (jax.random.truncated_normal(kk, -3, 3, shape, f32)
                * jnp.asarray(1.0 / np.sqrt(fan_in), f32))

    return {
        "embed": (jax.random.normal(k[0], (V, d), f32)
                  * jnp.asarray(1.0 / np.sqrt(d), f32)),
        "w_gate": tn(k[1], (L, d, ff), d),
        "w_in": tn(k[2], (L, d, ff), d),
        "w_out": tn(k[3], (L, ff, d), ff),
        "wk": tn(k[4], (L, d, hkv), d),
        "wo": tn(k[5], (L, hq, d), hq),
        "wq": tn(k[6], (L, d, hq), d),
        "wv": tn(k[7], (L, d, hkv), d),
    }


def _layer_norm(x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps)


def _rope(x, theta):
    """x: (s, heads, dh); rotate the two halves of each head."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(cfg, ein, p, x):
    s = x.shape[0]
    H, KV, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    h = _layer_norm(x)
    q = ein("sd,de->se", h, p["wq"]).reshape(s, H, dh)
    k = ein("sd,de->se", h, p["wk"]).reshape(s, KV, dh)
    v = ein("sd,de->se", h, p["wv"]).reshape(s, KV, dh)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    rep = H // KV
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = ein("qhd,khd->hqk", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = ein("hqk,khd->qhd", w, v).reshape(s, H * dh)
    x = x + ein("se,ed->sd", o, p["wo"])
    h = _layer_norm(x)
    a = jax.nn.silu(ein("sd,df->sf", h, p["w_in"]))
    g = ein("sd,df->sf", h, p["w_gate"])
    return x + ein("sf,fd->sd", a * g, p["w_out"])


def row_loss_sum(cfg, precision, params, ids, labels):
    """Summed cross-entropy of one sequence: ids, labels (s,)."""
    ein = einsum(precision)
    x = params["embed"][ids]
    block = jax.checkpoint(partial(_block, cfg, ein))
    for layer in range(cfg["n_layers"]):
        x = block({n: params[n][layer] for n in BLOCK}, x)
    x = _layer_norm(x)
    logits = ein("sd,vd->sv", x, params["embed"])
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - ll)


def stacked(keys: Tuple) -> int:
    """Block weights lead with their layer axis."""
    return int(keys[0] in BLOCK)


def name_of(path: str, index: Tuple[int, ...]) -> str:
    """``wq`` of layer 2 is ``layers/2/mix/wq``; ``embed`` is ``embed``."""
    if path in BLOCK:
        return f"layers/{index[0]}/{BLOCK[path]}/{path}"
    return path
