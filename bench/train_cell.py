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
(``bench/reference/training.py`` training the model of
``bench/reference/<reference>.py``) runs the checked steps from the same
seed; the check compares, by the worst matrix of the worst replica, each
side's matrices named by ``bench/names.py``'s rule:

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
import typing

import numpy as np

from bench import names

ADAM_B1 = 0.9


def _dataclass_in(tp):
    """The dataclass a field's declared type names (``Optional[X]`` too),
    or None."""
    if dataclasses.is_dataclass(tp):
        return tp
    return next((a for a in typing.get_args(tp)
                 if dataclasses.is_dataclass(a)), None)


def _field_value(tp, value, where: str):
    sub = _dataclass_in(tp)
    if sub is not None and isinstance(value, dict):
        hints = typing.get_type_hints(sub)
        unknown = sorted(set(value) - set(hints))
        if unknown:
            raise ValueError(f"{where}: {sub.__name__} has no field "
                             f"{', '.join(map(repr, unknown))}")
        return sub(**{k: _field_value(hints[k], v, f"{where}.{k}")
                      for k, v in value.items()})
    if isinstance(value, list):
        return tuple(value)
    return value


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` of a configuration file: every field
    the file gives, each sub-config object (``moe``, ``mla``, ...) as the
    dataclass its field declares, lists as tuples, and ``arch_type``
    "dense" unless the file gives one.  A key inside a sub-config that its
    dataclass lacks raises; top-level keys that are no field (``kind``,
    ``reference``, ``published``, ``reduced``, ``notes``, ...) describe the
    configuration and are left out."""
    from repro.configs.base import ModelConfig
    hints = typing.get_type_hints(ModelConfig)
    kw = {"arch_type": "dense"}
    for f in dataclasses.fields(ModelConfig):
        if f.name != "name" and f.name in cfg:
            kw[f.name] = _field_value(hints[f.name], cfg[f.name], f.name)
    return ModelConfig(name=name, **kw)


def _program_stacked(tree):
    """``stacked`` of the program's parameter tree: a leaf under ``scan``
    leads with its layer axis (the scan's steps); a routed expert's weight,
    a leaf beside ``router`` other than the router, then with its expert
    axis."""
    import jax
    paths = {names.keys_of(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}

    def stacked(keys):
        expert = (keys[-1] != "router"
                  and keys[:-1] + ("router",) in paths)
        return int(keys[0] == "scan") + int(expert)
    return stacked


def program_norms(tree) -> dict:
    """Per leaf of a params-shaped tree whose leaves lead with the replica
    axis, under its joined key path: the L2 norms of its matrices, per
    replica, per layer of a scanned leaf and per expert of a routed weight
    (traceable; :func:`program_matrices` names them)."""
    return names.path_norms(tree, _program_stacked(tree))


def program_matrices(norms: dict) -> dict:
    """:func:`program_norms` named as ``bench/names.py`` names matrices.

    The program holds its blocks in ``prefix`` (a list of layers), ``scan``
    (a list of the layers of one unit, each leaf stacked over the units)
    and ``tail`` (a list of layers): with P prefix layers and U layers a
    unit, ``scan/u`` at unit j is layer P + j * U + u, and ``tail/i``
    follows the last unit."""
    paths = [p.split("/") for p in norms]
    prefix = len({p[1] for p in paths if p[0] == "prefix"})
    unit = len({p[1] for p in paths if p[0] == "scan"})
    units = max((np.asarray(norms[p]).shape[1] for p in norms
                 if p.startswith("scan/")), default=0)

    def name_of(path, index):
        keys = path.split("/")
        if keys[0] == "prefix":
            layer = int(keys[1])
        elif keys[0] == "scan":
            layer = prefix + index[0] * unit + int(keys[1])
            index = index[1:]
        elif keys[0] == "tail":
            layer = prefix + units * unit + int(keys[1])
        else:
            return names.join([path, *index])
        return names.join(["layers", layer, *keys[2:], *index])
    return names.named(norms, name_of)


def gaps(prog: dict, ref: dict, keep_from=None) -> float:
    """Worst |program - reference| norm over max(reference norm, median
    reference norm of the replica), over the matrices kept; both sides
    named alike (``bench/names.py``)."""
    names_p, p = names.flat(prog)
    names_r, r = names.flat(ref)
    if names_p != names_r:
        raise RuntimeError(f"matrix names differ: {names_p} vs {names_r}")
    med = np.median(r, axis=1, keepdims=True)
    rel = np.abs(p - r) / np.maximum(r, med)
    if keep_from is not None:
        _, g = names.flat(keep_from)
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

    from bench.reference import training
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
            grad1 = {k: v / np.float32(1 - ADAM_B1) for k, v in
                     program_matrices(norms(state.opt.mu)).items()}
    params0 = jax.jit(lambda k: init(k).params, out_shardings=p_sh)(key)
    diff = jax.jit(lambda a, b: program_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))
    change = program_matrices(diff(state.params, params0))
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
    t_ref = time.perf_counter()
    ref = training.train(reference(cell), cell.config, tr["policy"],
                         tr["lr"], key, host[:checked], replicas=R,
                         devices=run.devices)
    ref_loss = ref["loss"].mean(axis=1)
    log(f"[{cell.name}] reference {checked} steps in "
        f"{time.perf_counter() - t_ref!r} s; losses {losses} vs "
        f"{ref_loss.tolist()}")
    run.checks += [
        ("loss_gap", float(np.max(np.abs(np.asarray(losses) - ref_loss))),
         cell.limits["loss_gap"]),
        ("grad_norm_gap", gaps(grad1, ref["grad"]),
         cell.limits["grad_norm_gap"]),
        ("change_norm_gap", gaps(change, ref["change"],
                                 keep_from=ref["grad"]),
         cell.limits["change_norm_gap"]),
    ]
    return bad == 0
