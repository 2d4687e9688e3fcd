"""Attention: GQA (head-TP and seq-TP layouts), MLA, sliding windows, caches.

Layouts (DESIGN.md §6) — the residual stream is always sequence-sharded
``(b, s/tp, d)`` over the ``model`` axis:

* **head-TP**: all-gather the sequence, project local q-heads (kv heads
  duplicated up to tp when n_kv < tp), attend, out-project to a partial sum,
  reduce-scatter back to ``s/tp``.
* **seq-TP** (head counts not divisible by tp): projections are replicated;
  q stays on the local sequence shard, k/v are all-gathered; no output
  collective.  Decode shards the KV cache over the model axis by *slot* and
  combines partial attention with a distributed logsumexp.

Causal self-attention over a whole sequence (train and prefill without
seq-TP) runs the Pallas flash kernel wherever Pallas is on (or interpreted)
and the kernel takes the shapes (``kernels/flash_attention``); every other
call, and every call with Pallas off (the CPU default), runs the jnp core,
chunked over query blocks and *banded* for sliding windows so compiled
FLOPs/memory stay honest.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as fa_ops
from repro.models.common import (ParamDef, ShardCtx, apply_rope, kv_eff_heads,
                                 softcap)

NEG_INF = -1e30
POS_SENTINEL = np.int32(2**30)   # k-slot "empty" marker (always masked out)
QKV = "attention_qkv"            # checkpoint name of the q, k, v projections


# ---------------------------------------------------------------------------
# Core attention (jnp oracle; chunked + banded)
# ---------------------------------------------------------------------------


@jax.named_scope("attention_core")
def attention_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                   window: Optional[int] = None,
                   cap: Optional[float] = None,
                   chunk: int = 512) -> jnp.ndarray:
    """Masked multi-head attention.

    q: (b, sq, kvh, G, dh)   — GQA: G query heads per kv head
    k,v: (b, skv, kvh, dh)
    q_pos: (sq,) or (b, sq); k_pos: (skv,) or (b, skv) — absolute positions;
    mask = (k_pos <= q_pos) & (k_pos > q_pos - window).

    Where both positions are 0..s-1, known while tracing, the flash kernel
    takes the call if it can (``fa_ops.kernel_takes``); else the jnp core
    below runs.
    """
    if fa_ops.kernel_takes(q, k, v, q_pos, k_pos):
        return fa_ops.flash_attention(q, k, v, window=window, cap=cap)
    b, sq, kvh, G, dh = q.shape
    skv = k.shape[1]
    scale = 1.0 / np.sqrt(dh)
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (b, sq))
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None], (b, skv))

    def attend(qc, qpc, kc, vc, kpc):
        # qc: (b, cq, kvh, G, dh); kc/vc: (b, sk, kvh, dh)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qc.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale
        s = softcap(s, cap)
        m = kpc[:, None, None, None, :] <= qpc[:, None, None, :, None]
        if window is not None:
            m &= kpc[:, None, None, None, :] > (qpc[:, None, None, :, None] - window)
        s = jnp.where(m, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        w = jnp.where(m.any(-1, keepdims=True), w, 0.0)   # fully-masked rows
        return jnp.einsum("bkgqs,bskd->bqkgd", w, vc.astype(jnp.float32)).astype(q.dtype)

    if sq <= chunk:
        return attend(q, q_pos, k, v, k_pos)

    n_chunks = sq // chunk
    if sq % chunk:
        raise ValueError(f"sq={sq} not divisible by chunk={chunk}")
    # banded k slice: chunk c needs k positions in (c*chunk - window, (c+1)*chunk)
    banded = window is not None and skv == sq and window + chunk < skv
    band = (min((window // chunk + 1) * chunk + chunk, skv)) if banded else skv

    qs = q.reshape(b, n_chunks, chunk, kvh, G, dh)
    qps = q_pos.reshape(b, n_chunks, chunk)

    def per_chunk(c):
        qc, qpc = qs[:, c], qps[:, c]
        if banded:
            start = jnp.clip(c * chunk + chunk - band, 0, skv - band)
            kc = lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vc = lax.dynamic_slice_in_dim(v, start, band, axis=1)
            kpc = lax.dynamic_slice_in_dim(k_pos, start, band, axis=1)
        else:
            kc, vc, kpc = k, v, k_pos
        return attend(qc, qpc, kc, vc, kpc)

    out = lax.map(per_chunk, jnp.arange(n_chunks))          # (n, b, chunk, ...)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, kvh, G, v.shape[-1])


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    d, hq, dh = cfg.d_model, cfg.n_heads, cfg.d_head
    if cfg.mla is not None:
        return mla_defs(cfg, tp)
    head_tp = cfg.tp_strategy == "head"
    sh1 = (None, "model") if head_tp else (None, None)
    sh0 = ("model", None) if head_tp else (None, None)
    if head_tp:
        kv_eff, rep = kv_eff_heads(cfg.n_kv_heads, tp)
    else:
        kv_eff, rep = cfg.n_kv_heads, 1
    defs = {
        "wq": ParamDef((d, hq * dh), sh1),
        "wk": ParamDef((d, kv_eff * dh), sh1,
                       init="kv_dup" if rep > 1 else "fan_in",
                       kv_base_heads=cfg.n_kv_heads, kv_rep=rep),
        "wv": ParamDef((d, kv_eff * dh), sh1,
                       init="kv_dup" if rep > 1 else "fan_in",
                       kv_base_heads=cfg.n_kv_heads, kv_rep=rep),
        "wo": ParamDef((hq * dh, d), sh0),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((dh,), (None,), init="ones")
        defs["k_norm"] = ParamDef((dh,), (None,), init="ones")
    return defs


def mla_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    m = cfg.mla
    d, hq = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    sh1 = (None, "model")
    return {
        "wq": ParamDef((d, hq * qd), sh1),
        "w_dkv": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim), (None, None)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), init="ones"),
        "w_uk": ParamDef((m.kv_lora_rank, hq * m.qk_nope_head_dim), sh1),
        "w_uv": ParamDef((m.kv_lora_rank, hq * m.v_head_dim), sh1),
        "wo": ParamDef((hq * m.v_head_dim, d), ("model", None)),
    }


# ---------------------------------------------------------------------------
# Cache definitions
# ---------------------------------------------------------------------------


def cache_defs(cfg: ModelConfig, tp: int, batch_local: int,
               capacity: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """Per-attention-layer KV cache (LOCAL shapes).  ``capacity`` is the ring
    size (min(seq_len, window) in long-context mode)."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "latent": jax.ShapeDtypeStruct((batch_local, capacity, m.kv_lora_rank), dt),
            "k_rope": jax.ShapeDtypeStruct((batch_local, capacity, m.qk_rope_head_dim), dt),
            "pos": jax.ShapeDtypeStruct((batch_local, capacity), jnp.int32),
        }
    dh = cfg.d_head
    if cfg.tp_strategy == "head":
        kv_eff, _ = kv_eff_heads(cfg.n_kv_heads, tp)
        kv_loc, cap_loc = kv_eff // tp, capacity
    else:   # seq-TP / replicated: shard cache slots over the model axis
        kv_loc = cfg.n_kv_heads
        cap_loc = capacity // tp if cfg.tp_strategy == "seq" else capacity
    return {
        "k": jax.ShapeDtypeStruct((batch_local, cap_loc, kv_loc, dh), dt),
        "v": jax.ShapeDtypeStruct((batch_local, cap_loc, kv_loc, dh), dt),
        "pos": jax.ShapeDtypeStruct((batch_local, cap_loc), jnp.int32),
    }


def empty_cache(defs: Dict[str, jax.ShapeDtypeStruct]) -> Dict[str, jnp.ndarray]:
    out = {}
    for k, s in defs.items():
        if k == "pos":
            out[k] = jnp.full(s.shape, POS_SENTINEL, dtype=s.dtype)
        else:
            out[k] = jnp.zeros(s.shape, s.dtype)
    return out


# ---------------------------------------------------------------------------
# GQA forward
# ---------------------------------------------------------------------------


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _qk_normalize(x, scale):
    """Qwen3/OLMoE-style per-head RMS norm over the head dim."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6)
    return (x * scale.astype(jnp.float32)).astype(dt)


def attn_fwd(cfg: ModelConfig, ctx: ShardCtx, p: Dict, x: jnp.ndarray, *,
             window: Optional[int], cache: Optional[Dict] = None,
             pos: Optional[jnp.ndarray] = None,
             ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x: (b, s_loc, d) seq-sharded residual.  Two modes:
       * full (pos None): train/prefill over the whole sequence; if `cache`
         is given (prefill), it is filled with the last `capacity` k/v.
       * decode (pos (b,)): single new token against the cache.
    Returns (y (b, s_loc, d), new_cache)."""
    if cfg.mla is not None:
        return mla_fwd(cfg, ctx, p, x, window=window, cache=cache, pos=pos)
    if pos is None:
        return _gqa_full(cfg, ctx, p, x, window=window, cache=cache)
    return _gqa_decode(cfg, ctx, p, x, window=window, cache=cache, pos=pos)


def _proj(x, w):
    """A q, k or v projection, named for the layer checkpoint: its output,
    taken before norms and RoPE, is what a saving policy keeps."""
    return checkpoint_name(x @ w, QKV)


def saved_bytes(cfg: ModelConfig, ctx: ShardCtx, batch: int, s_loc: int) -> int:
    """Bytes of what ``_gqa_full`` names for the layer checkpoint over a
    residual of (batch, s_loc, d): the q, k and v projections (``QKV``)
    and, where the flash kernel takes the call, its output and f32
    logsumexp (``fa_ops.RESIDUALS``).  MLA names nothing."""
    if cfg.mla is not None:
        return 0
    head_tp = cfg.tp_strategy == "head" and ctx.model_axis is not None
    seq_tp = cfg.tp_strategy == "seq" and ctx.model_axis is not None
    dh, it = cfg.d_head, jnp.dtype(cfg.dtype).itemsize
    if head_tp:
        kv_eff, _ = kv_eff_heads(cfg.n_kv_heads, ctx.tp)
        hq, kv, s = cfg.n_heads // ctx.tp, kv_eff // ctx.tp, s_loc * ctx.tp
    else:
        hq, kv, s = cfg.n_heads, cfg.n_kv_heads, s_loc
    n = batch * s * (hq + 2 * kv) * dh * it
    if not seq_tp and fa_ops.kernel_runs(s, dh, dh):
        n += batch * hq * s * (dh * it + 4)
    return n


def _gqa_full(cfg, ctx, p, x, *, window, cache):
    head_tp = cfg.tp_strategy == "head" and ctx.model_axis is not None
    seq_tp = cfg.tp_strategy == "seq" and ctx.model_axis is not None
    b, s_loc, d = x.shape
    dh = cfg.d_head
    tp = ctx.tp if (head_tp or seq_tp) else 1
    s = s_loc * (ctx.tp if (head_tp or seq_tp) else 1)

    if head_tp:
        hq_loc = cfg.n_heads // ctx.tp
        kv_eff, _ = kv_eff_heads(cfg.n_kv_heads, ctx.tp)
        kv_loc = kv_eff // ctx.tp
        xg = ctx.gather_seq(x, compress=cfg.compress_gathers)   # (b, s, d)
        q = _split_heads(_proj(xg, p["wq"]), hq_loc, dh)
        k = _split_heads(_proj(xg, p["wk"]), kv_loc, dh)
        v = _split_heads(_proj(xg, p["wv"]), kv_loc, dh)
        positions = np.arange(s, dtype=np.int32)     # known while tracing
        q_pos = k_pos = positions
    else:
        hq_loc, kv_loc = cfg.n_heads, cfg.n_kv_heads
        local_pos = (ctx.index() * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
                     if seq_tp else np.arange(s_loc, dtype=np.int32))
        q = _split_heads(_proj(x, p["wq"]), hq_loc, dh)
        k_loc = _split_heads(_proj(x, p["wk"]), kv_loc, dh)
        v_loc = _split_heads(_proj(x, p["wv"]), kv_loc, dh)
        if cfg.qk_norm:
            q = _qk_normalize(q, p["q_norm"])
            k_loc = _qk_normalize(k_loc, p["k_norm"])
        k_loc = apply_rope(k_loc, local_pos, cfg.rope_theta)
        q = apply_rope(q, local_pos, cfg.rope_theta)
        k = ctx.gather_seq(k_loc) if seq_tp else k_loc       # (b, s, kv, dh)
        v = ctx.gather_seq(v_loc) if seq_tp else v_loc
        q_pos = local_pos
        k_pos = np.arange(s, dtype=np.int32)

    if head_tp:
        if cfg.qk_norm:
            q = _qk_normalize(q, p["q_norm"])
            k = _qk_normalize(k, p["k_norm"])
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, k_pos, cfg.rope_theta)

    G = hq_loc // kv_loc
    qg = q.reshape(b, q.shape[1], kv_loc, G, dh)
    o = attention_core(qg, k, v, q_pos, k_pos, window=window,
                       cap=cfg.attn_softcap)
    o = o.reshape(b, o.shape[1], hq_loc * dh)

    if head_tp:
        y = o @ p["wo"]                                      # (b, s, d) partial
        y = ctx.scatter_seq(y)                               # (b, s_loc, d)
    else:
        y = o @ p["wo"]                                      # (b, s_loc, d)

    new_cache = None
    if cache is not None:
        new_cache = _fill_cache_from_full(cfg, ctx, cache, k, v, k_pos,
                                          head_tp=head_tp, seq_tp=seq_tp)
    return y, new_cache


def _fill_cache_from_full(cfg, ctx, cache, k, v, k_pos, *, head_tp, seq_tp):
    """Prefill: write the last `capacity` keys into the ring cache."""
    capacity_total = cache["pos"].shape[1] * (ctx.tp if seq_tp else 1)
    s = k.shape[1]
    take = min(s, capacity_total)
    k_last, v_last = k[:, s - take:], v[:, s - take:]
    pos_last = k_pos[s - take:]
    slots = pos_last % capacity_total                        # (take,)
    b = k.shape[0]
    ring_k = jnp.zeros((b, capacity_total) + k.shape[2:], k.dtype)
    ring_v = jnp.zeros_like(ring_k)
    ring_p = jnp.full((b, capacity_total), POS_SENTINEL, jnp.int32)
    ring_k = ring_k.at[:, slots].set(k_last)
    ring_v = ring_v.at[:, slots].set(v_last)
    ring_p = ring_p.at[:, slots].set(jnp.broadcast_to(pos_last[None], (b, take)))
    if seq_tp:   # keep only this device's slot shard
        cap_loc = cache["pos"].shape[1]
        start = ctx.index() * cap_loc
        ring_k = lax.dynamic_slice_in_dim(ring_k, start, cap_loc, axis=1)
        ring_v = lax.dynamic_slice_in_dim(ring_v, start, cap_loc, axis=1)
        ring_p = lax.dynamic_slice_in_dim(ring_p, start, cap_loc, axis=1)
    return {"k": ring_k.astype(cache["k"].dtype),
            "v": ring_v.astype(cache["v"].dtype),
            "pos": ring_p}


def _ring_insert(cache_arr, new, slot):
    """cache (b, C, …); new (b, 1, …); slot (b,) — one-hot blend write."""
    C = cache_arr.shape[1]
    onehot = jnp.arange(C, dtype=jnp.int32)[None, :] == slot[:, None]   # (b, C)
    oh = onehot.reshape(onehot.shape + (1,) * (cache_arr.ndim - 2))
    return jnp.where(oh, new.astype(cache_arr.dtype), cache_arr)


def _gqa_decode(cfg, ctx, p, x, *, window, cache, pos):
    """x: (b, 1, d); pos: (b,) absolute position of the new token."""
    head_tp = cfg.tp_strategy == "head" and ctx.model_axis is not None
    seq_tp = cfg.tp_strategy == "seq" and ctx.model_axis is not None
    b = x.shape[0]
    dh = cfg.d_head
    if head_tp:
        hq_loc = cfg.n_heads // ctx.tp
        kv_eff, _ = kv_eff_heads(cfg.n_kv_heads, ctx.tp)
        kv_loc = kv_eff // ctx.tp
    else:
        hq_loc, kv_loc = cfg.n_heads, cfg.n_kv_heads

    q = _split_heads(x @ p["wq"], hq_loc, dh)                # (b, 1, hq_loc, dh)
    k_new = _split_heads(x @ p["wk"], kv_loc, dh)
    v_new = _split_heads(x @ p["wv"], kv_loc, dh)
    if cfg.qk_norm:
        q = _qk_normalize(q, p["q_norm"])
        k_new = _qk_normalize(k_new, p["k_norm"])
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)

    cap_loc = cache["pos"].shape[1]
    capacity_total = cap_loc * (ctx.tp if seq_tp else 1)
    slot = (pos % capacity_total).astype(jnp.int32)          # (b,)

    if seq_tp:
        # cache slots sharded over the model axis: write if the slot is mine
        start = ctx.index() * cap_loc
        local_slot = slot - start
        mine = (local_slot >= 0) & (local_slot < cap_loc)
        safe = jnp.clip(local_slot, 0, cap_loc - 1)
        kc = _ring_insert(cache["k"], k_new, safe)
        kc = jnp.where(mine[:, None, None, None], kc, cache["k"])
        vc = _ring_insert(cache["v"], v_new, safe)
        vc = jnp.where(mine[:, None, None, None], vc, cache["v"])
        pc = _ring_insert(cache["pos"], pos[:, None], safe)
        pc = jnp.where(mine[:, None], pc, cache["pos"])
        new_cache = {"k": kc, "v": vc, "pos": pc}
        o = _distributed_decode_attend(cfg, ctx, q, kc, vc, pc, pos, window)
    else:
        kc = _ring_insert(cache["k"], k_new, slot)
        vc = _ring_insert(cache["v"], v_new, slot)
        pc = _ring_insert(cache["pos"], pos[:, None], slot)
        new_cache = {"k": kc, "v": vc, "pos": pc}
        G = hq_loc // kv_loc
        qg = q.reshape(b, 1, kv_loc, G, dh)
        o = attention_core(qg, kc, vc, pos[:, None], pc,
                           window=window, cap=cfg.attn_softcap)
        o = o.reshape(b, 1, hq_loc * dh)

    y = o @ p["wo"]
    if head_tp:
        y = ctx.psum_model(y)                                # (b, 1, d)
    return y, new_cache


def _distributed_decode_attend(cfg, ctx, q, k_loc, v_loc, kpos_loc, pos, window):
    """Partial attention over the local cache shard + distributed logsumexp
    combine over the model axis (seq-TP decode)."""
    b, _, hq, dh = q.shape
    kv = k_loc.shape[2]
    G = hq // kv
    scale = 1.0 / np.sqrt(dh)
    qf = q.reshape(b, kv, G, dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,bckd->bkgc", qf, k_loc.astype(jnp.float32)) * scale
    s = softcap(s, cfg.attn_softcap)
    m = kpos_loc[:, None, None, :] <= pos[:, None, None, None]
    if window is not None:
        m &= kpos_loc[:, None, None, :] > (pos[:, None, None, None] - window)
    s = jnp.where(m, s, NEG_INF)
    local_max = jnp.max(s, axis=-1)                          # (b, kv, G)
    gmax = ctx.pmax_model(local_max)
    w = jnp.exp(s - gmax[..., None]) * m
    den = ctx.psum_model(jnp.sum(w, axis=-1))                # (b, kv, G)
    num = ctx.psum_model(
        jnp.einsum("bkgc,bckd->bkgd", w, v_loc.astype(jnp.float32)))
    o = num / jnp.maximum(den[..., None], 1e-30)
    return o.reshape(b, 1, hq * dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA forward (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_fwd(cfg, ctx, p, x, *, window, cache, pos):
    m = cfg.mla
    head_tp = ctx.model_axis is not None
    b, s_loc, d = x.shape
    hq_loc = cfg.n_heads // (ctx.tp if head_tp else 1)
    nope, rope_d, vd, r = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                           m.v_head_dim, m.kv_lora_rank)
    from repro.models.common import rmsnorm

    if pos is None:
        xg = ctx.gather_seq(x) if head_tp else x             # (b, s, d)
        s = xg.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        q = _split_heads(xg @ p["wq"], hq_loc, nope + rope_d)
        qn, qr = q[..., :nope], q[..., nope:]
        qr = apply_rope(qr, positions, cfg.rope_theta)
        dkv = xg @ p["w_dkv"]                                # (b, s, r+rope)
        latent = rmsnorm(dkv[..., :r], p["kv_norm"])
        k_rope = apply_rope(dkv[..., None, r:], positions, cfg.rope_theta)  # (b,s,1,rope)
        kn = _split_heads(latent @ p["w_uk"], hq_loc, nope)
        vv = _split_heads(latent @ p["w_uv"], hq_loc, vd)
        k = jnp.concatenate([kn, jnp.broadcast_to(k_rope, kn.shape[:-1] + (rope_d,))], -1)
        # GQA form: kv heads = hq_loc, G = 1 (attention_core allows v_dim != qk_dim)
        qg = jnp.concatenate([qn, qr], -1).reshape(b, s, hq_loc, 1, nope + rope_d)
        o = attention_core(qg, k, vv, q_pos=positions,
                           k_pos=positions, window=window, cap=cfg.attn_softcap)
        o = o.reshape(b, s, hq_loc * vd)
        y = o @ p["wo"]
        y = ctx.scatter_seq(y) if head_tp else y
        new_cache = None
        if cache is not None:
            new_cache = _fill_mla_cache(cache, latent, k_rope[:, :, 0, :], positions)
        return y, new_cache
    return _mla_decode(cfg, ctx, p, x, window=window, cache=cache, pos=pos)


def _fill_mla_cache(cache, latent, rope_post, positions):
    """Store the last `capacity` latents + post-rope rotary keys in the ring."""
    b, s, r = latent.shape
    capacity = cache["pos"].shape[1]
    take = min(s, capacity)
    lat, rp = latent[:, s - take:], rope_post[:, s - take:]
    pos_last = positions[s - take:]
    slots = pos_last % capacity
    ring_lat = jnp.zeros((b, capacity, r), cache["latent"].dtype).at[:, slots].set(
        lat.astype(cache["latent"].dtype))
    ring_rope = jnp.zeros((b, capacity, rp.shape[-1]), cache["k_rope"].dtype
                          ).at[:, slots].set(rp.astype(cache["k_rope"].dtype))
    ring_pos = jnp.full((b, capacity), POS_SENTINEL, jnp.int32).at[:, slots].set(
        jnp.broadcast_to(pos_last[None], (b, take)))
    return {"latent": ring_lat, "k_rope": ring_rope, "pos": ring_pos}


def _mla_decode(cfg, ctx, p, x, *, window, cache, pos):
    """Absorbed low-rank MLA decode: scores and values stay in latent space."""
    m = cfg.mla
    head_tp = ctx.model_axis is not None
    b = x.shape[0]
    hq_loc = cfg.n_heads // (ctx.tp if head_tp else 1)
    nope, rope_d, vd, r = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                           m.v_head_dim, m.kv_lora_rank)
    from repro.models.common import rmsnorm

    q = _split_heads(x @ p["wq"], hq_loc, nope + rope_d)     # (b,1,h,qd)
    qn, qr = q[..., :nope], q[..., nope:]
    qr = apply_rope(qr, pos[:, None], cfg.rope_theta)
    dkv = x @ p["w_dkv"]
    latent_new = rmsnorm(dkv[..., :r], p["kv_norm"])         # (b,1,r)
    krope_new = apply_rope(dkv[..., None, r:], pos[:, None], cfg.rope_theta)[:, :, 0]

    capacity = cache["pos"].shape[1]
    slot = (pos % capacity).astype(jnp.int32)
    lat_c = _ring_insert(cache["latent"], latent_new, slot)
    rope_c = _ring_insert(cache["k_rope"], krope_new, slot)
    pos_c = _ring_insert(cache["pos"], pos[:, None], slot)
    new_cache = {"latent": lat_c, "k_rope": rope_c, "pos": pos_c}

    # absorb W_uk into q: (b,1,h,nope) @ (r, h*nope) -> (b,h,r)
    w_uk = p["w_uk"].reshape(r, hq_loc, nope)
    qlat = jnp.einsum("bhn,rhn->bhr", qn[:, 0].astype(jnp.float32),
                      w_uk.astype(jnp.float32))
    scale = 1.0 / np.sqrt(nope + rope_d)
    s_lat = jnp.einsum("bhr,bcr->bhc", qlat, lat_c.astype(jnp.float32))
    s_rope = jnp.einsum("bhd,bcd->bhc", qr[:, 0].astype(jnp.float32),
                        rope_c.astype(jnp.float32))
    s = (s_lat + s_rope) * scale
    s = softcap(s, cfg.attn_softcap)
    mask = pos_c[:, None, :] <= pos[:, None, None]
    if window is not None:
        mask &= pos_c[:, None, :] > (pos[:, None, None] - window)
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhc,bcr->bhr", w, lat_c.astype(jnp.float32))  # (b,h,r)
    w_uv = p["w_uv"].reshape(r, hq_loc, vd)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(jnp.float32))
    o = o.reshape(b, 1, hq_loc * vd).astype(x.dtype)
    y = o @ p["wo"]
    if head_tp:
        y = ctx.psum_model(y)
    return y, new_cache
