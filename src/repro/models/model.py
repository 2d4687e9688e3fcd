"""Model assembly: blocks → scan groups → language model.

Layer grouping: the per-layer metadata (mixer kind, attention window, MoE?)
repeats with a short period (1 for uniform stacks, 2 for Gemma-2's
local/global alternation, 3 for RecurrentGemma's rec/rec/attn).  Layers are
stacked per unit-position and iterated with ``lax.scan`` (keeps the HLO and
compile times small at 40+ layers); non-periodic leading/trailing layers
(DeepSeek's dense layer 0, RecurrentGemma's 38 = 12·3 + 2 tail) are unrolled
prefix/tail.

Forward modes:
  * full   — train / prefill: sequence-sharded residual (b, s/tp, d)
  * decode — one token (b, 1, d) against per-layer caches

The LM head is vocab-sharded; cross-entropy uses a distributed logsumexp
over the model axis, chunked over the sequence so the (b, s, V/tp) logits
are never materialized at once.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as fa_ops
from repro.models import attention as attn
from repro.models import moe as ffn
from repro.models import recurrent as rec
from repro.models.common import (ParamDef, ShardCtx, apply_norm, norm_defs)

PyTree = Any


# ---------------------------------------------------------------------------
# Layer metadata
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerMeta:
    kind: str                     # "attn" | "rec"
    window: Optional[int]         # attention window (None = full)
    use_moe: bool
    d_ff: int                     # dense FFN width (0 = no FFN)


def layer_metas(cfg: ModelConfig, long_ctx: bool = False) -> List[LayerMeta]:
    kinds = cfg.layer_kinds()
    metas = []
    attn_idx = 0
    for i, kind in enumerate(kinds):
        window = None
        if kind == "attn":
            if cfg.attn_kind == "swa":
                window = cfg.window
            elif cfg.attn_kind == "alternating":
                window = cfg.window if attn_idx % 2 == 0 else None
            attn_idx += 1
            if long_ctx and window is None:
                window = cfg.long_context_window   # bounded-memory long-context mode
        use_moe = cfg.moe is not None and kind == "attn" and i >= cfg.moe.first_dense_layers
        if cfg.moe is not None and not use_moe and kind == "attn":
            d_ff = cfg.moe.d_ff_dense
        else:
            d_ff = cfg.d_ff
        metas.append(LayerMeta(kind, window, use_moe, d_ff))
    return metas


def group_layers(cfg: ModelConfig, metas: List[LayerMeta],
                 ) -> Tuple[List[LayerMeta], List[LayerMeta], int, List[LayerMeta]]:
    """-> (prefix, unit, n_units, tail)."""
    start = cfg.moe.first_dense_layers if cfg.moe is not None else 0
    period = len(cfg.layer_pattern)
    if cfg.attn_kind == "alternating":
        period = int(np.lcm(period, 2))
    body = metas[start:]
    n_units = len(body) // period
    tail_start = start + n_units * period
    prefix = metas[:start]
    unit = metas[start:start + period] if n_units else []
    tail = metas[tail_start:]
    return prefix, unit, n_units, tail


# ---------------------------------------------------------------------------
# Layer checkpoint
# ---------------------------------------------------------------------------

RESIDUAL = "attention_residual"   # checkpoint name of the residual after attention
# What a layer checkpoint under SAVE_MATMULS keeps beyond its inputs: the
# outputs of the matmuls whose inputs the backward needs again (q, k, v; w_in
# and w_gate), the flash kernel's output and logsumexp, and the residual
# after attention (so that wo does not run again).  Norms, RoPE, the head
# transposes and the activation stay recomputed.
SAVED_NAMES = (attn.QKV, fa_ops.RESIDUALS, RESIDUAL, ffn.MLP_IN)
SAVE_MATMULS = jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)


def _checkpointed(f, remat: bool, policy):
    """The layer checkpoint around a unit of layers: none without
    ``remat``; else ``policy`` says what the backward keeps (None: the
    unit's inputs alone, so its whole forward runs again)."""
    return jax.checkpoint(f, policy=policy) if remat else f


def saved_bytes(cfg: ModelConfig, ctx: ShardCtx, batch: int, seq: int) -> int:
    """Bytes that SAVE_MATMULS keeps in a train step over a local batch of
    ``batch`` sequences of ``seq`` tokens: what SAVED_NAMES name in every
    layer under the unit checkpoint (the unrolled prefix and tail layers
    have none)."""
    _, unit, n_units, _ = group_layers(cfg, layer_metas(cfg))
    mctx = _mixer_ctx(cfg, ctx)
    seq_sharded = (cfg.tp_strategy in ("head", "seq", "seq_ssm")
                   and ctx.model_axis is not None)
    s_loc = seq // ctx.tp if seq_sharded else seq
    residual = batch * s_loc * cfg.d_model * jnp.dtype(cfg.dtype).itemsize
    n = 0
    for meta in unit:
        if meta.kind == "attn":
            n += residual + attn.saved_bytes(cfg, mctx, batch, s_loc)
        if meta.d_ff and not meta.use_moe:
            n += ffn.mlp_saved_bytes(cfg, mctx, batch, s_loc, meta.d_ff)
    return n_units * n


# ---------------------------------------------------------------------------
# Block defs / fwd
# ---------------------------------------------------------------------------


def block_defs(cfg: ModelConfig, tp: int, meta: LayerMeta) -> Dict:
    defs: Dict[str, Any] = {"ln1": norm_defs(cfg.norm_kind, cfg.d_model)}
    if meta.kind == "attn":
        defs["mix"] = attn.attn_defs(cfg, tp)
    elif cfg.recurrent.kind == "rglru":
        defs["mix"] = rec.rglru_defs(cfg, tp)
    else:
        defs["mix"] = rec.mamba2_defs(cfg, tp)
    if cfg.post_norm:
        defs["post_ln1"] = norm_defs(cfg.norm_kind, cfg.d_model)
    if meta.d_ff or meta.use_moe:
        defs["ln2"] = norm_defs(cfg.norm_kind, cfg.d_model)
        defs["ffn"] = (ffn.moe_defs(cfg, tp) if meta.use_moe
                       else ffn.mlp_defs(cfg, tp, d_ff=meta.d_ff))
        if cfg.post_norm:
            defs["post_ln2"] = norm_defs(cfg.norm_kind, cfg.d_model)
    return defs


def block_fwd(cfg: ModelConfig, ctx: ShardCtx, mixer_ctx: ShardCtx,
              meta: LayerMeta, p: Dict, x: jnp.ndarray, *,
              cache: Optional[Dict], pos: Optional[jnp.ndarray],
              ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (x, new_cache, aux_loss)."""
    seqpar = pos is None
    h = apply_norm(cfg.norm_kind, x, p["ln1"])
    if meta.kind == "attn":
        with jax.named_scope("attention"):
            mix, new_cache = attn.attn_fwd(cfg, mixer_ctx, p["mix"], h,
                                           window=meta.window, cache=cache,
                                           pos=pos)
    elif cfg.recurrent.kind == "rglru":
        mix, new_cache = rec.rglru_fwd(cfg, mixer_ctx, p["mix"], h,
                                       cache=cache, pos=pos)
    else:
        mix, new_cache = rec.mamba2_fwd(cfg, mixer_ctx, p["mix"], h,
                                        cache=cache, pos=pos)
    if cfg.post_norm:
        mix = apply_norm(cfg.norm_kind, mix, p["post_ln1"])
    x = x + mix
    if meta.kind == "attn":
        x = checkpoint_name(x, RESIDUAL)
    aux = jnp.zeros((), jnp.float32)
    if meta.d_ff or meta.use_moe:
        h = apply_norm(cfg.norm_kind, x, p["ln2"])
        with jax.named_scope("mlp"):
            if meta.use_moe:
                y, aux = ffn.moe_fwd(cfg, mixer_ctx, p["ffn"], h)
            else:
                y = ffn.mlp_fwd(cfg, mixer_ctx, p["ffn"], h,
                                sequence_parallel=seqpar)
        if cfg.post_norm:
            y = apply_norm(cfg.norm_kind, y, p["post_ln2"])
        x = x + y
    return x, new_cache, aux


def block_cache_defs(cfg: ModelConfig, tp: int, meta: LayerMeta,
                     batch_local: int, capacity: int):
    if meta.kind == "attn":
        cap = min(capacity, meta.window) if meta.window else capacity
        return attn.cache_defs(cfg, tp, batch_local, cap)
    if cfg.recurrent.kind == "rglru":
        return rec.rglru_cache_defs(cfg, tp, batch_local)
    return rec.mamba2_cache_defs(cfg, tp, batch_local)


# ---------------------------------------------------------------------------
# Whole-model defs
# ---------------------------------------------------------------------------


def stack_defs(defs: PyTree, n: int) -> PyTree:
    def s(d: ParamDef) -> ParamDef:
        shard = d.shard if d.shard else (None,) * len(d.shape)
        return dataclasses.replace(d, shape=(n,) + tuple(d.shape),
                                   shard=(None,) + tuple(shard))
    return jax.tree.map(s, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def model_defs(cfg: ModelConfig, tp: int, long_ctx: bool = False) -> Dict:
    metas = layer_metas(cfg, long_ctx)
    prefix, unit, n_units, tail = group_layers(cfg, metas)
    d = cfg.d_model
    vp = cfg.padded_vocab(tp)
    defs: Dict[str, Any] = {
        "embed": ParamDef((vp, d), ("model", None), init="embed",
                          scale=1.0 / np.sqrt(d)),
        "final_norm": norm_defs(cfg.norm_kind, d),
        "prefix": [block_defs(cfg, tp, m) for m in prefix],
        "scan": (stack_defs([block_defs(cfg, tp, m) for m in unit], n_units)
                 if n_units else []),
        "tail": [block_defs(cfg, tp, m) for m in tail],
    }
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((d, vp), (None, "model"))
    return defs


def model_cache_defs(cfg: ModelConfig, tp: int, batch_local: int,
                     capacity: int, long_ctx: bool = False) -> Dict:
    metas = layer_metas(cfg, long_ctx)
    prefix, unit, n_units, tail = group_layers(cfg, metas)

    def stack(c):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_units,) + s.shape, s.dtype), c)

    return {
        "prefix": [block_cache_defs(cfg, tp, m, batch_local, capacity) for m in prefix],
        "scan": [stack(block_cache_defs(cfg, tp, m, batch_local, capacity))
                 for m in unit],
        "tail": [block_cache_defs(cfg, tp, m, batch_local, capacity) for m in tail],
    }


def empty_cache_tree(defs: PyTree) -> PyTree:
    def mk(s: jax.ShapeDtypeStruct):
        if s.dtype == jnp.int32:
            return jnp.full(s.shape, attn.POS_SENTINEL, s.dtype)
        return jnp.zeros(s.shape, s.dtype)
    return jax.tree.map(mk, defs)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _mixer_ctx(cfg: ModelConfig, ctx: ShardCtx) -> ShardCtx:
    # replicated strategy: mixers/FFN see no model axis (vocab still sharded);
    # seq_ssm keeps the axis (the SSD state prefix-combine needs it)
    return ShardCtx() if cfg.tp_strategy == "replicated" else ctx


def embed_tokens(cfg: ModelConfig, ctx: ShardCtx, params: Dict,
                 ids: jnp.ndarray, seq_shard: bool) -> jnp.ndarray:
    """Vocab-parallel embedding.  ids: (b, s) — REPLICATED over the model
    axis (each shard masked-looks-up its vocab slice for all tokens).  The
    partial embeddings are merged with a reduce-scatter straight into the
    sequence-parallel residual layout (Megatron-SP) or a psum when the
    residual stays full-sequence."""
    table = params["embed"]
    if ctx.model_axis is not None:
        vloc = table.shape[0]
        start = ctx.index() * vloc
        loc = ids - start
        ok = (loc >= 0) & (loc < vloc)
        e = jnp.where(ok[..., None], table[jnp.clip(loc, 0, vloc - 1)], 0)
        e = ctx.scatter_seq(e) if seq_shard else ctx.psum_model(e)
    else:
        e = table[ids]
    if cfg.norm_kind == "gemma_rmsnorm":            # gemma scales embeddings
        e = e * jnp.asarray(np.sqrt(cfg.d_model), e.dtype)
    return e.astype(jnp.dtype(cfg.dtype))


def _frontend_override(cfg: ModelConfig, ctx: ShardCtx, x: jnp.ndarray,
                       extra_emb: Optional[jnp.ndarray],
                       positions: jnp.ndarray) -> jnp.ndarray:
    """Replace the first n_embeds positions with provided frontend embeddings
    (VLM patches / audio conditioning) — DESIGN.md §5."""
    if cfg.frontend is None or extra_emb is None:
        return x
    n = cfg.frontend.n_embeds
    idx = jnp.clip(positions, 0, n - 1)                       # (s_loc,)
    override = jnp.take(extra_emb, idx, axis=1).astype(x.dtype)
    return jnp.where((positions < n)[None, :, None], override, x)


def forward(cfg: ModelConfig, ctx: ShardCtx, params: Dict, ids: jnp.ndarray, *,
            extra_emb: Optional[jnp.ndarray] = None,
            caches: Optional[Dict] = None,
            pos: Optional[jnp.ndarray] = None,
            long_ctx: bool = False,
            remat: bool = True,
            remat_policy=None,
            unroll: bool = False,
            ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (hidden (b, s_loc, d), new_caches, aux_loss).  With
    ``remat``, each scanned unit runs under a checkpoint whose
    ``remat_policy`` (a ``jax.checkpoint`` policy; None recomputes the
    whole unit) says what the backward keeps."""
    metas = layer_metas(cfg, long_ctx)
    prefix, unit, n_units, tail = group_layers(cfg, metas)
    mctx = _mixer_ctx(cfg, ctx)
    compute_dt = jnp.dtype(cfg.dtype)
    params = jax.tree.map(lambda a: a.astype(compute_dt)
                          if a.dtype == jnp.float32 and a.ndim > 1 else a, params)

    seq_sharded = (pos is None
                   and cfg.tp_strategy in ("head", "seq", "seq_ssm")
                   and ctx.model_axis is not None)
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, ctx, params, ids, seq_shard=seq_sharded)
    if pos is None:
        s_loc = x.shape[1]
        positions = (ctx.index() * s_loc if seq_sharded else 0) + jnp.arange(
            s_loc, dtype=jnp.int32)
        x = _frontend_override(cfg, ctx, x, extra_emb, positions)

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, list] = {"prefix": [], "scan": [], "tail": []}

    def run_block(meta, p, x, cache):
        return block_fwd(cfg, ctx, mctx, meta, p, x, cache=cache, pos=pos)

    with jax.named_scope("blocks"):
        # --- prefix (unrolled) ----------------------------------------------
        for i, meta in enumerate(prefix):
            c = caches["prefix"][i] if caches is not None else None
            x, nc, aux = run_block(meta, params["prefix"][i], x, c)
            aux_total += aux
            new_caches["prefix"].append(nc)

        # --- scanned units --------------------------------------------------
        if n_units and unroll:
            # python-loop over units: big HLO, but per-layer FLOPs/collectives
            # appear explicitly (cost_analysis counts while-loop bodies ONCE)
            unit_params = params["scan"]

            def unit_fn(x, aux_acc, p_unit, c_unit):
                ncs = []
                for j, meta in enumerate(unit):
                    x, nc, aux = run_block(meta, p_unit[j], x, c_unit[j])
                    aux_acc = aux_acc + aux
                    ncs.append(nc)
                return x, aux_acc, ncs

            for u in range(n_units):
                p_unit = jax.tree.map(lambda a: a[u], unit_params)
                c_unit = (jax.tree.map(lambda a: a[u], caches["scan"])
                          if caches is not None else [None] * len(unit))
                x, aux_total, ncs = _checkpointed(unit_fn, remat, remat_policy)(
                    x, aux_total, p_unit, c_unit)
                if caches is not None:
                    new_caches["scan"].append(ncs)
            if caches is not None:
                # restack unit caches to the (n_units, ...) layout scan produces
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                       *new_caches["scan"])
                new_caches["scan"] = stacked
        elif n_units:
            unit_params = params["scan"]
            if caches is None:

                def unit_body(carry, p_unit):
                    x, aux_acc = carry
                    for j, meta in enumerate(unit):
                        x, _, aux = run_block(meta, p_unit[j], x, None)
                        aux_acc = aux_acc + aux
                    return (x, aux_acc), None

                body = _checkpointed(unit_body, remat, remat_policy)
                (x, aux_total), _ = lax.scan(body, (x, aux_total), unit_params)
            else:

                def unit_body_c(carry, xs_):
                    x, aux_acc = carry
                    p_unit, c_unit = xs_
                    ncs = []
                    for j, meta in enumerate(unit):
                        x, nc, aux = run_block(meta, p_unit[j], x, c_unit[j])
                        aux_acc = aux_acc + aux
                        ncs.append(nc)
                    return (x, aux_acc), ncs

                body = _checkpointed(unit_body_c, remat, remat_policy)
                (x, aux_total), scan_caches = lax.scan(
                    body, (x, aux_total), (unit_params, caches["scan"]))
                new_caches["scan"] = scan_caches

        # --- tail (unrolled) ------------------------------------------------
        for i, meta in enumerate(tail):
            c = caches["tail"][i] if caches is not None else None
            x, nc, aux = run_block(meta, params["tail"][i], x, c)
            aux_total += aux
            new_caches["tail"].append(nc)

    x = apply_norm(cfg.norm_kind, x, params["final_norm"])
    return x, (new_caches if caches is not None else None), aux_total


# ---------------------------------------------------------------------------
# Head / loss / decode
# ---------------------------------------------------------------------------


def head_matrix(cfg: ModelConfig, params: Dict) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return params["embed"].T          # (d, V_loc)
    return params["head"]


def lm_loss(cfg: ModelConfig, ctx: ShardCtx, params: Dict, ids: jnp.ndarray,
            labels: jnp.ndarray, *, extra_emb: Optional[jnp.ndarray] = None,
            remat: bool = True, remat_policy=None, chunk: int = 256,
            unroll: bool = False,
            ) -> Tuple[jnp.ndarray, Dict]:
    """Mean next-token cross-entropy (labels < 0 are masked).

    ids/labels: (b, s) — full sequence, replicated over the model axis
    (the embedding reduce-scatters into the seq-parallel residual).
    """
    x, _, aux = forward(cfg, ctx, params, ids, extra_emb=extra_emb,
                        remat=remat, remat_policy=remat_policy, unroll=unroll)
    with jax.named_scope("lm_head"):
        w = head_matrix(cfg, params).astype(x.dtype)

        # Vocab-parallel cross-entropy: logits are vocab-sharded, so every model
        # shard needs ALL tokens — gather the sequence-sharded residual first,
        # then reduce the logsumexp over the model axis.
        seq_sharded = (cfg.tp_strategy in ("head", "seq", "seq_ssm")
                       and ctx.model_axis is not None)
        if seq_sharded:
            x = ctx.gather_seq(x, compress=cfg.compress_gathers)
        b, s, d = x.shape

        n_chunks = max(1, s // chunk)
        cs = s // n_chunks
        xs = x[:, :n_chunks * cs].reshape(b, n_chunks, cs, d).swapaxes(0, 1)
        ls = labels[:, :n_chunks * cs].reshape(b, n_chunks, cs).swapaxes(0, 1)

        vloc = w.shape[1]
        start = ctx.index() * vloc

        def chunk_loss(xc, lc):
            logits = (xc @ w).astype(jnp.float32)                 # (b, cs, V_loc)
            if cfg.final_softcap is not None:
                logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
            # max-shift is a constant wrt the gradient (softmax is shift
            # invariant) — pmax has no JVP rule, so sever the tangent first
            mx = ctx.pmax_model(lax.stop_gradient(logits.max(-1)))
            se = ctx.psum_model(jnp.exp(logits - mx[..., None]).sum(-1))
            lse = mx + jnp.log(se)
            loc = lc - start
            ok = (loc >= 0) & (loc < vloc)
            ll = jnp.take_along_axis(logits, jnp.clip(loc, 0, vloc - 1)[..., None],
                                     axis=-1)[..., 0]
            ll = ctx.psum_model(jnp.where(ok, ll, 0.0))
            mask = (lc >= 0).astype(jnp.float32)
            return jnp.sum((lse - ll) * mask), jnp.sum(mask)

        fn = jax.checkpoint(chunk_loss) if remat else chunk_loss

        def body(acc, inp):
            l, n = fn(*inp)
            return (acc[0] + l, acc[1] + n), None

        (tot, n), _ = lax.scan(body, (jnp.zeros((), jnp.float32),
                                      jnp.zeros((), jnp.float32)), (xs, ls))
        # after the gather every model shard summed over the SAME tokens (the
        # per-token lse/ll were completed with psum inside chunk_loss)
        loss = tot / jnp.maximum(n, 1.0)
    metrics = {"xent": loss, "aux": aux, "tokens": n}
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_coef * aux
    return loss, metrics


def prefill(cfg: ModelConfig, ctx: ShardCtx, params: Dict, ids: jnp.ndarray,
            capacity: int, *, extra_emb: Optional[jnp.ndarray] = None,
            long_ctx: bool = False, unroll: bool = False,
            ) -> Tuple[jnp.ndarray, Dict]:
    """Run the full prompt, fill caches, return last-position logits."""
    b, s_loc = ids.shape
    cache_defs = model_cache_defs(cfg, ctx.tp if ctx.model_axis else 1, b,
                                  capacity, long_ctx)
    caches = empty_cache_tree(cache_defs)
    x, new_caches, _ = forward(cfg, ctx, params, ids, extra_emb=extra_emb,
                               caches=caches, long_ctx=long_ctx, remat=False,
                               unroll=unroll)
    last = x[:, -1:, :]
    if (cfg.tp_strategy in ("head", "seq", "seq_ssm")
            and ctx.model_axis is not None):
        # the last position lives on the last seq shard: gather it
        lastg = ctx.gather_seq(last, axis=1)
        last = lastg[:, -1:, :]
    logits = (last @ head_matrix(cfg, params).astype(last.dtype)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits[:, 0], new_caches


def decode_step(cfg: ModelConfig, ctx: ShardCtx, params: Dict,
                ids: jnp.ndarray, pos: jnp.ndarray, caches: Dict, *,
                long_ctx: bool = False, unroll: bool = False,
                ) -> Tuple[jnp.ndarray, Dict]:
    """One decode step.  ids: (b, 1); pos: (b,).  Returns (logits (b, V_loc),
    new caches)."""
    x, new_caches, _ = forward(cfg, ctx, params, ids, caches=caches, pos=pos,
                               long_ctx=long_ctx, remat=False, unroll=unroll)
    logits = (x @ head_matrix(cfg, params).astype(x.dtype)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits[:, 0], new_caches


def sample_greedy(ctx: ShardCtx, logits_loc: jnp.ndarray) -> jnp.ndarray:
    """Greedy sampling with vocab-sharded logits: global argmax via pmax."""
    vloc = logits_loc.shape[-1]
    local_best = jnp.max(logits_loc, axis=-1)
    local_idx = jnp.argmax(logits_loc, axis=-1) + ctx.index() * vloc
    gbest = ctx.pmax_model(local_best)
    winner = jnp.where(local_best >= gbest, local_idx, -1)
    return ctx.pmax_model(winner).astype(jnp.int32)
