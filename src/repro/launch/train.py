"""Training driver.

With several devices it runs one data-parallel replica on each; on CPU it
runs single-device with the reduced ("smoke") architecture variants, which
is what the end-to-end example uses:

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \\
        --steps 200 --consistency cvap --staleness 4 --vthr 0.05
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import (ARCHS, ConsistencySpec, TrainConfig, get_config,
                           reduced_config)
from repro.core.sync import force_sync
from repro.data import SyntheticLM, batches
from repro.launch import mesh as mesh_lib
from repro.launch import specs as S
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.state import init_train_state


def run(tcfg: TrainConfig, cfg, mesh=None, batch_size: int = 8,
        seq_len: int = 64, log=print):
    """Train for ``tcfg.steps`` steps; returns (state, history).

    Each logged step's history entry holds its metrics plus ``step_s``, the
    host time from dispatch until its outputs are ready (exact when the
    step before it was logged too); the first entry also holds
    ``compile_s``.  With a mesh, the state is built directly in its
    ``train_state_pspecs`` shardings."""
    dp = mesh_lib.dp_size(mesh) if mesh is not None else 1
    tp = mesh_lib.tp_size(mesh) if mesh is not None else 1
    shardings = (S.shardings(S.train_state_pspecs(cfg, tcfg, tp), mesh)
                 if mesh is not None else None)
    state = init_train_state(cfg, tcfg, tp=tp, dp=dp,
                             key=jax.random.key(tcfg.seed),
                             shardings=shardings)
    step_fn = steps.make_train_step(cfg, tcfg, mesh)
    source = SyntheticLM(cfg.vocab_size, seed=tcfg.seed)
    it = batches(source, batch_size, seq_len)
    history = []
    t0 = time.time()
    rng = np.random.default_rng(tcfg.seed)
    compile_s = None
    for i in range(tcfg.steps):
        b = next(it)
        batch = {"ids": jnp.asarray(b["ids"]), "labels": jnp.asarray(b["labels"])}
        if cfg.frontend is not None:
            batch["extra_emb"] = jnp.asarray(
                rng.normal(0, 0.02, (batch_size, cfg.frontend.n_embeds,
                                     cfg.d_model)), jnp.dtype(cfg.dtype))
        if compile_s is None:
            t = time.perf_counter()
            step_fn = step_fn.lower(state, batch).compile()
            compile_s = time.perf_counter() - t
        t_step = time.perf_counter()
        state, metrics = step_fn(state, batch)
        if i % tcfg.log_every == 0 or i == tcfg.steps - 1:
            jax.block_until_ready((state, metrics))
            m = {k: float(v) for k, v in metrics.items()}
            m["step_s"] = time.perf_counter() - t_step
            m["step"] = i
            m["wall"] = time.time() - t0
            if i == 0:
                m["compile_s"] = compile_s
            history.append(m)
            log(f"step {i:5d} loss={m['loss']:.4f} xent={m['xent']:.4f} "
                f"synced={m['synced']:.0f} lr={m['lr']:.2e} "
                f"step_s={m['step_s']:.4f}")
        if (tcfg.checkpoint_dir and tcfg.checkpoint_every
                and i and i % tcfg.checkpoint_every == 0):
            _checkpoint(tcfg, state, i)
    if tcfg.checkpoint_dir:
        _checkpoint(tcfg, state, tcfg.steps)
    return state, history


def _checkpoint(tcfg: TrainConfig, state, step: int) -> None:
    # sync replicas first: checkpoints hold the fully-synchronized state
    params = jax.tree.map(lambda x: x[0], state.params)
    sync = jax.tree.map(lambda x: x[0], state.sync)
    params, _ = force_sync(params, sync, ())
    save_checkpoint(tcfg.checkpoint_dir, step, params,
                    metadata={"arch": tcfg.arch,
                              "consistency": tcfg.consistency.model})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced CPU-runnable variant")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adam",
                    choices=["sgd", "momentum", "adam"])
    ap.add_argument("--consistency", default="bsp",
                    choices=["bsp", "ssp", "cap", "vap", "cvap"])
    ap.add_argument("--staleness", type=int, default=0)
    ap.add_argument("--vthr", type=float, default=0.0)
    ap.add_argument("--quantize-sync", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--history-out", default="")
    args = ap.parse_args()

    cfg = reduced_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, dtype="float32")
    tcfg = TrainConfig(
        arch=args.arch, steps=args.steps, lr=args.lr, optimizer=args.optimizer,
        seed=args.seed,
        consistency=ConsistencySpec(model=args.consistency,
                                    staleness=args.staleness,
                                    value_bound=args.vthr),
        quantize_sync=args.quantize_sync,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
    )
    enable_compile_cache()
    # data-parallel replicas over every visible device
    n_dev = len(jax.devices())
    mesh = (mesh_lib.make_mesh((n_dev, 1), ("data", "model"))
            if n_dev > 1 else None)
    _, history = run(tcfg, cfg, mesh=mesh, batch_size=args.batch,
                     seq_len=args.seq)
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=2)


if __name__ == "__main__":
    main()
