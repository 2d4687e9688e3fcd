"""Train cells: ``make_train_step``'s compiled step on ``init_train_state``'s
state, fed pre-generated batches, one replica per chip.

Set-up builds the state on the device in one jitted call from the seed,
compiles the step, and drives that same step and state through the first
``checked_steps`` steps on batches whose rows all differ, reading what the
check needs on the way: each step's loss, the first gradient as Adam got it
(its first moment after one step, over 1 - beta1), and the parameters'
change after the last of these steps.  The window then runs the same call
on the same state in a closed loop, reading each step's loss.  After the
window the program's state is freed and the plain reference
(``bench/reference/<reference>.py``) runs the checked steps from the same
seed; the check compares, by the worst matrix of the worst replica:

* ``loss_gap``: |program loss - reference loss| of each checked step;
* ``grad_norm_gap``: |program norm - reference norm| of the first
  gradient, over the larger of the reference's norm of that matrix and of
  its median matrix;
* ``change_norm_gap``: the same for the parameters' change after the
  checked steps, over the matrices whose reference gradient is at least a
  thousandth of the median matrix's.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

ADAM_B1 = 0.9


def model_config(cfg: dict, name: str):
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items()
          if k in fields and k not in ("name", "source")}
    return ModelConfig(name=name, arch_type="dense", source=cfg["source"],
                       **kw)


def program_norms(tree) -> dict:
    """Per replica and per matrix (per layer for scanned block weights)
    L2 norms of a params-shaped tree whose leaves lead with the replica
    axis; named as the reference names them."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        name = str(keys[-1])
        lead = 2 if keys[0] == "scan" else 1
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                     axis=tuple(range(lead, x.ndim))))
    return out


def gaps(prog: dict, ref: dict, flat, keep_from=None) -> float:
    """Worst |program - reference| norm over max(reference norm, median
    reference norm of the replica), over the matrices kept."""
    names_p, p = flat(prog)
    names_r, r = flat(ref)
    if names_p != names_r:
        raise RuntimeError(f"matrix names differ: {names_p} vs {names_r}")
    med = np.median(r, axis=1, keepdims=True)
    rel = np.abs(p - r) / np.maximum(r, med)
    if keep_from is not None:
        _, g = flat(keep_from)
        rel = np.where(g >= 1e-3 * np.median(g, axis=1, keepdims=True),
                       rel, 0.0)
    return float(np.max(rel))


def run(run) -> bool:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ConsistencySpec, TrainConfig
    from repro.launch import mesh as mesh_lib
    from repro.launch import specs as S
    from repro.launch import steps as steps_lib
    from repro.launch.state import init_train_state

    from bench.run import generator, log, memory_peak, reference, run_key

    cell, tr = run.cell, run.cell.traffic
    R = cell.chips
    cfg = model_config(cell.config, cell.config.get("arch", cell.name))
    tcfg = TrainConfig(arch=cfg.name, steps=1, lr=tr["lr"],
                       warmup_steps=tr["warmup_steps"],
                       optimizer=tr["optimizer"], log_every=1,
                       consistency=ConsistencySpec(**tr["policy"]))
    key = run_key(run.seed)
    mesh = mesh_lib.make_mesh((R, 1), ("data", "model")) if R > 1 else None
    if mesh is None:
        def init(k):
            return init_train_state(cfg, tcfg, 1, 1, k)
        state = jax.jit(init)(key)
        in_sh = jax.sharding.SingleDeviceSharding(run.devices[0])
        p_sh = None
    else:
        shardings = S.shardings(S.train_state_pspecs(cfg, tcfg, 1), mesh)
        state = init_train_state(cfg, tcfg, tp=1, dp=R, key=key,
                                 shardings=shardings)
        in_sh = NamedSharding(mesh, P("data", None))
        p_sh = shardings.params

        def init(k):
            return init_train_state(cfg, tcfg, 1, R, k)
    step = steps_lib.make_train_step(cfg, tcfg, mesh)

    B = tr["batch_per_chip"] * R
    host = generator(cell).batches(tr, cfg.vocab_size, B,
                                   tr["pool_batches"], run.seed)
    pool = [{"ids": jax.device_put(b[:, :-1], in_sh),
             "labels": jax.device_put(b[:, 1:], in_sh)} for b in host]
    checked = int(tr["checked_steps"])

    norms = jax.jit(program_norms)
    losses = []
    grad1 = None
    for i in range(checked):
        state, m = step(state, pool[i])
        losses.append(float(m["loss"]))
        if i == 0:
            grad1 = {k: np.asarray(v) / np.float32(1 - ADAM_B1)
                     for k, v in norms(state.opt.mu).items()}
    params0 = jax.jit(lambda k: init(k).params, out_shardings=p_sh)(key)
    diff = jax.jit(lambda a, b: program_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))
    change = {k: np.asarray(v) for k, v in diff(state.params,
                                                params0).items()}
    del params0
    jax.block_until_ready(state)

    tokens_per_step = B * tr["seq_len"]
    n = bad = 0
    i = checked
    gc.collect()
    with run.profiled():
        run.mark_setup_done()
        t_open = time.perf_counter()
        with run.span("bench.window"):
            while True:
                with run.span("bench.step_dispatch"):
                    state, m = step(state, pool[i % len(pool)])
                with run.span("bench.read_loss"):
                    loss = float(m["loss"])
                n += 1
                i += 1
                bad += not math.isfinite(loss)
                if time.perf_counter() - t_open >= run.seconds:
                    break
        t_close = time.perf_counter()
    run.window_s = t_close - t_open
    run.attempted, run.failed = n, bad
    run.end_to_end["tokens_per_s"] = n * tokens_per_step / run.window_s
    run.data.update(steps=n, tokens_per_step=tokens_per_step,
                    seq_len=tr["seq_len"], model=cell.config)
    run.memory_peak_bytes = memory_peak(run.devices)
    log(f"[{cell.name}] setup_s={run.setup_s!r} steps={n} "
        f"window_s={run.window_s!r} tokens_per_s="
        f"{run.end_to_end['tokens_per_s']!r} "
        f"memory_peak_bytes={run.memory_peak_bytes} "
        f"compiles_in_window={run.compiles_between(t_open, t_close)}")

    del state, m, pool
    gc.collect()
    ref_mod = reference(cell)
    t_ref = time.perf_counter()
    ref = ref_mod.train(cell.config, tr["policy"], tr["lr"], key,
                        host[:checked], replicas=R, devices=run.devices)
    ref_loss = ref["loss"].mean(axis=1)
    log(f"[{cell.name}] reference {checked} steps in "
        f"{time.perf_counter() - t_ref!r} s; losses {losses} vs "
        f"{ref_loss.tolist()}")
    run.checks += [
        ("loss_gap", float(np.max(np.abs(np.asarray(losses) - ref_loss))),
         cell.limits["loss_gap"]),
        ("grad_norm_gap", gaps(grad1, ref["grad"], ref_mod.flat),
         cell.limits["grad_norm_gap"]),
        ("change_norm_gap", gaps(change, ref["change"], ref_mod.flat,
                                 keep_from=ref["grad"]),
         cell.limits["change_norm_gap"]),
    ]
    return bad == 0
