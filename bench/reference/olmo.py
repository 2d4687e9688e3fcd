"""Plain reference of data-parallel training of a dense decoder LM.

Written from the published description of OLMo (arXiv:2402.00838) and of
the paper's consistency models (arXiv:1312.7869), in plain ``jax.numpy``
at float32 with every matmul at ``Precision.HIGHEST``.  It imports nothing
of the system under test.

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
are stacked over layers, one draw per stacked matrix.

Training: every replica computes the gradient of its own rows (in blocks of
one row, each layer recomputed in the backward pass so that a block fits),
takes a local Adam step ``u`` from its own moments, applies it to its own
parameters and adds it to its unsynchronized delta; when the policy
triggers (every step for BSP; after ``s + 1`` steps or when any replica's
max |delta| exceeds ``v`` for CVAP) every replica adds the sum of all
replicas' deltas minus its own, and the deltas restart from zero.  With one
replica that sync is the identity.

``precision="fp8"`` is the control: every matmul input and every gradient
flowing back into one is rounded to float8 e4m3 with a per-tensor scale.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
BLOCK_LEAVES = ("w_gate", "w_in", "w_out", "wk", "wo", "wq", "wv")
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------


def _round_f8(x):
    s = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX)
    return (x / s).astype(F8).astype(x.dtype) * s


@jax.custom_vjp
def q8(x):
    return _round_f8(x)


def _q8_fwd(x):
    return _round_f8(x), None


def _q8_bwd(_, g):
    return (_round_f8(g),)


q8.defvjp(_q8_fwd, _q8_bwd)


def _ein(precision: str):
    if precision == "f32":
        return partial(jnp.einsum, precision=HI)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, q8(a), q8(b), precision=HI)
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


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
    ein = _ein(precision)
    x = params["embed"][ids]
    block = jax.checkpoint(partial(_block, cfg, ein))
    for layer in range(cfg["n_layers"]):
        x = block({n: params[n][layer] for n in BLOCK_LEAVES}, x)
    x = _layer_norm(x)
    logits = ein("sd,vd->sv", x, params["embed"])
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - ll)


def replica_grad(cfg, precision, params, ids, labels):
    """Mean loss and its gradient over one replica's rows (b, s), one row
    at a time."""
    zero = jax.tree.map(jnp.zeros_like, params)
    vg = jax.value_and_grad(partial(row_loss_sum, cfg, precision))

    def body(carry, row):
        g_acc, l_acc = carry
        loss, g = vg(params, row[0], row[1])
        return (jax.tree.map(jnp.add, g_acc, g), l_acc + loss), None

    (g, tot), _ = lax.scan(body, (zero, jnp.zeros((), jnp.float32)),
                           (ids, labels))
    n = ids.shape[0] * ids.shape[1]
    return tot / n, jax.tree.map(lambda a: a / n, g)


# ---------------------------------------------------------------------------
# training with replicas
# ---------------------------------------------------------------------------


def _adam(g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return -lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Per replica and per weight matrix (per layer for block leaves):
    L2 norms.  Leaves carry a leading replica axis."""
    out = {}
    for name, x in tree.items():
        if name in BLOCK_LEAVES:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=(2, 3)))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x),
                                         axis=tuple(range(1, x.ndim))))
    return out


def _policy(policy: dict):
    """(clock bounded, value bounded, staleness bound, value bound)."""
    model = policy["model"]
    clock_bounded = model in ("bsp", "ssp", "cap", "cvap")
    value_bounded = model in ("vap", "cvap")
    if not (clock_bounded or value_bounded):
        raise ValueError(f"no reference for consistency model {model!r}")
    s_bound = 0 if model == "bsp" else int(policy.get("staleness", 0))
    return (clock_bounded, value_bounded, s_bound,
            float(policy.get("value_bound", 0.0)))


def make_step(cfg: dict, replicas: int, clock_bounded: bool,
              value_bounded: bool, precision: str = "f32"):
    """One training step of every replica: (params, m, v, delta, steps
    since the last sync, Adam step t, learning rate, staleness bound, value
    bound, ids, labels) -> (params, m, v, delta, per-replica loss,
    per-replica gradient, synced).  Every array leads with the replica
    axis; ``delta`` is None for one replica."""
    grads = jax.vmap(partial(replica_grad, cfg, precision))

    def step(params, m, v, delta, since, t, lr, s_bound, v_bound, ids,
             labels):
        loss, g = grads(params, ids, labels)
        upd = jax.tree.map(lambda gg, mm, vv: _adam(gg, mm, vv, t, lr),
                           g, m, v)
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda x: x[i], upd, is_leaf=lambda x: isinstance(x, tuple))
        u, m, v = pick(0), pick(1), pick(2)
        params = jax.tree.map(jnp.add, params, u)
        synced = jnp.ones((), bool)
        if delta is not None:
            delta = jax.tree.map(jnp.add, delta, u)
            trig = jnp.zeros((), bool)
            if clock_bounded:
                trig |= since + 1 >= s_bound + 1
            if value_bounded:
                mx = jnp.max(jnp.stack([jnp.max(jnp.abs(x))
                                        for x in jax.tree.leaves(delta)]))
                trig |= mx > v_bound
            tot = jax.tree.map(lambda x: jnp.sum(x, 0, keepdims=True), delta)
            params = jax.tree.map(
                lambda p, t_, d: jnp.where(trig, p + (t_ - d), p),
                params, tot, delta)
            delta = jax.tree.map(lambda d: jnp.where(trig, 0.0 * d, d), delta)
            synced = trig
        return params, m, v, delta, loss, g, synced

    return jax.jit(step, donate_argnums=(0, 1, 2, 3))


def train(cfg: dict, policy: dict, lr: float, key, batches: Sequence[np.ndarray],
          replicas: int, precision: str = "f32", devices=None) -> dict:
    """Run ``len(batches)`` steps from the seeded initial point.

    ``batches``: host arrays (global_batch, seq + 1) of token ids; replica r
    takes rows [r * b, (r + 1) * b).  Returns per-step mean losses, the
    first step's gradient norms and the parameters' change after the last
    step, per replica and per matrix (see :func:`leaf_norms`)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    R = replicas
    devs = list(devices if devices is not None else jax.devices()[:R])
    rep = NamedSharding(Mesh(np.array(devs[:R]), ("r",)), P("r"))

    def init(k):
        p = init_params(cfg, k)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape), p)

    params = jax.jit(init, out_shardings=rep)(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=rep)
    m, v = zeros(params), zeros(params)
    delta = zeros(params) if R > 1 else None
    clock_bounded, value_bounded, s_bound, v_bound = _policy(policy)
    step = make_step(cfg, R, clock_bounded, value_bounded, precision)
    norms = jax.jit(leaf_norms)
    change = jax.jit(lambda p, k: leaf_norms(
        jax.tree.map(jnp.subtract, p, init(k))))
    losses: List[np.ndarray] = []
    first_grad = None
    since = 0
    for i, batch in enumerate(batches):
        b = batch.shape[0] // R
        ids = jax.device_put(batch[:, :-1].reshape(R, b, -1), rep)
        labels = jax.device_put(batch[:, 1:].reshape(R, b, -1), rep)
        params, m, v, delta, loss, g, synced = step(
            params, m, v, delta, np.int32(since), np.float32(i + 1),
            np.float32(lr), np.int32(s_bound), np.float32(v_bound), ids,
            labels)
        since = 0 if bool(synced) else since + 1
        losses.append(np.asarray(loss))
        if i == 0:
            first_grad = {k: np.asarray(x) for k, x in norms(g).items()}
        del g
    moved = {k: np.asarray(x) for k, x in change(params, key).items()}
    return {"loss": np.stack(losses), "grad": first_grad, "change": moved}


def flat(norms: Dict[str, np.ndarray]) -> Tuple[List[str], np.ndarray]:
    """Names and a (replicas, matrices) array of per-matrix norms."""
    names, cols = [], []
    for name in sorted(norms):
        x = np.asarray(norms[name])
        if x.ndim == 2:
            for layer in range(x.shape[1]):
                names.append(f"{name}.{layer}")
                cols.append(x[:, layer])
        else:
            names.append(name)
            cols.append(x)
    return names, np.stack(cols, axis=1)
