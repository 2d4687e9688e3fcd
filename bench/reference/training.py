"""Plain reference of data-parallel training under a consistency policy,
for any model whose reference supplies its weights and its loss.

Written from the published description of the paper's consistency models
(arXiv:1312.7869) and of Adam, in plain ``jax.numpy`` at float32 with
every matmul at ``Precision.HIGHEST``.  It imports nothing of the system
under test.

A model's reference (``bench/reference/<model>.py``) supplies:

* ``init_params(cfg, key)``: its initial weights from the run's key, a dict
  of float32 arrays;
* ``row_loss_sum(cfg, precision, params, ids, labels)``: the summed
  next-token cross-entropy of one sequence, its matmuls through
  :func:`einsum` of ``precision``;
* ``stacked(keys)`` and ``name_of(path, index)``: how many stacked axes
  (layers, experts) lead the leaf at ``keys``, and the name of each of its
  matrices by ``bench/names.py``'s rule.

Training: every replica computes the gradient of its own rows (one row at
a time, the model recomputing what it checkpoints), takes a local Adam step
``u`` from its own moments, applies it to its own parameters and adds it
to its unsynchronized delta; when the policy triggers (every step for BSP;
after ``s + 1`` steps or when any replica's max |delta| exceeds ``v`` for
CVAP) every replica adds the sum of all replicas' deltas minus its own, and
the deltas restart from zero.  With one replica that sync is the identity.

``precision="fp8"`` is the control: every matmul input and every gradient
flowing back into one is rounded to float8 e4m3 with a per-tensor scale.
The other forms that ``bench/control.py`` reads are arguments of
:func:`train`: a learning rate of 0 (a step that leaves the state
unchanged), half of each replica's rows, a policy that never syncs.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import names

HI = lax.Precision.HIGHEST
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


def einsum(precision: str):
    """The reference's matmul: float32 at ``HIGHEST``, or the float8
    control."""
    if precision == "f32":
        return partial(jnp.einsum, precision=HI)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, q8(a), q8(b), precision=HI)
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# gradients and the optimizer
# ---------------------------------------------------------------------------


def replica_grad(row_loss_sum, cfg, precision, params, ids, labels):
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


def _adam(g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return -lr * mhat / (jnp.sqrt(vhat) + eps), m, v


# ---------------------------------------------------------------------------
# training with replicas
# ---------------------------------------------------------------------------


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


def make_step(model, cfg: dict, clock_bounded: bool, value_bounded: bool,
              precision: str = "f32"):
    """One training step of every replica: (params, m, v, delta, steps
    since the last sync, Adam step t, learning rate, staleness bound, value
    bound, ids, labels) -> (params, m, v, delta, per-replica loss,
    per-replica gradient, synced).  Every array leads with the replica
    axis; ``delta`` is None for one replica."""
    grads = jax.vmap(partial(replica_grad, model.row_loss_sum, cfg,
                             precision))

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


def train(model, cfg: dict, policy: dict, lr: float, key,
          batches: Sequence[np.ndarray], replicas: int,
          precision: str = "f32", devices=None) -> dict:
    """Run ``len(batches)`` steps of ``model`` from the seeded initial
    point.

    ``batches``: host arrays (global_batch, seq + 1) of token ids; replica r
    takes rows [r * b, (r + 1) * b).  Returns per-step mean losses
    (steps, replicas), and the first step's gradient norms and the
    parameters' change after the last step, one (replicas,) array per
    matrix, named by ``bench/names.py``'s rule."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    R = replicas
    devs = list(devices if devices is not None else jax.devices()[:R])
    rep = NamedSharding(Mesh(np.array(devs[:R]), ("r",)), P("r"))

    def init(k):
        p = model.init_params(cfg, k)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape), p)

    def leaf_norms(tree):
        return names.path_norms(tree, model.stacked)

    params = jax.jit(init, out_shardings=rep)(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=rep)
    m, v = zeros(params), zeros(params)
    delta = zeros(params) if R > 1 else None
    clock_bounded, value_bounded, s_bound, v_bound = _policy(policy)
    step = make_step(model, cfg, clock_bounded, value_bounded, precision)
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
            first_grad = names.named(norms(g), model.name_of)
        del g
    moved = names.named(change(params, key), model.name_of)
    return {"loss": np.stack(losses), "grad": first_grad, "change": moved}
