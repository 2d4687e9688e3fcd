#!/usr/bin/env python3
"""Smoke test of the two parts of this system that run on a TPU.

    python chip_smoke.py             # one chip: train phase, then PS phase
    python chip_smoke.py --chips 4   # four chips: data-parallel replicas only

Train phase: ``repro.launch.train.run`` (``make_train_step``) on OLMo-1B at
its published widths, depth cut to the layers one chip holds, for a few
steps under BSP and under CVAP(3, 0.05).  Checks: every loss and gradient
norm is finite, ``synced`` follows the policy, and the initial loss on a
short sequence matches the same forward pass run on the host CPU backend.

PS phase: ``PSRuntime(RuntimeConfig(..., ps_kernels=True))`` with 4 worker
threads and 2 shards, applying Zipf-skewed integer count deltas to an f32
word-topic table of the NYTimes vocabulary through ``kernels/ps_apply`` on
the device.  Checks: the final master equals x0 + the sum of every update
bitwise, and the runtime reports no consistency violation.

Four-chip phase: the same OLMo cut as 4 data-parallel replicas on a
("data", "model") = (4, 1) mesh, with the policy-triggered delta
all-reduce.  Checks: BSP's first-step loss matches a one-device run of the
same global batch, BSP replicas stay identical, CVAP keeps the VAP
invariant on every replica and syncs at least every s + 1 steps.

The script exits non-zero, and prints no result, without a TPU, when
Pallas kernels are not compiled for it (REPRO_PALLAS=interpret/off), or
when any check fails.  Its last stdout line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# OLMo-1B (configs/olmo_1b.py, arXiv:2402.00838) keeps every published
# width: d_model 2048, 16 heads x 128, d_ff 8192, vocab 50,304, bf16
# compute over f32 master weights.  Only depth is cut: 4 of its 16 layers,
# one stage of a 4-stage pipeline.  f32 weights, Adam's two moments and the
# sync delta take 16 bytes a parameter (5.5 GiB for the cut); the v5e
# compile of the train step refuses 6 layers at this batch (19.7 GiB).
OLMO_LAYERS = 4
SEQ = 2048            # OLMo's context length
BATCH = 8             # per chip; the v5e compile refuses 12
STEPS = 6
PARITY_SEQ = 128      # the short sequence of the CPU-reference check
# Per-token losses of the bf16 forward carry bf16 rounding (eps 2^-8); the
# TPU and the CPU round, fuse and accumulate differently, so the two mean
# losses (about ln 50,304 = 10.8) agree to a few parts in a thousand, not
# bitwise.  1e-2 absolute is under 0.1% of the loss.
PARITY_TOL = 1e-2
# dp=4 vs dp=1 first-step loss: the same global batch, computed as four
# replica means that are averaged.  Same bf16 argument as above.
DP_PARITY_TOL = 1e-2
# BSP replicas apply the same summed delta each step, but each adds it to
# its own p + u_i, so they can differ by f32 rounding only.
REPLICA_TOL = 1e-5
FOUR_CHIP_BATCH = 8   # global: 2 per chip, so the dp=1 reference fits one chip

# The NYTimes bag-of-words vocabulary (UCI), the paper's LDA evaluation.
PS_ROWS = 102_660
PS_TOPICS = 128
PS_TOKENS = 8192      # tokens a worker resamples per clock (~25 documents)
PS_CLOCKS = 5
PS_WORKERS = 4
PS_SHARDS = 2


class SmokeFailure(AssertionError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def olmo_chip_share(layers: int = OLMO_LAYERS):
    from repro.configs import get_config
    return dataclasses.replace(get_config("olmo-1b"), n_layers=layers)


def _train_config(policy: str, steps: int, seed: int):
    from repro.configs import ConsistencySpec, TrainConfig
    spec = (ConsistencySpec(model="bsp") if policy == "bsp"
            else ConsistencySpec(model="cvap", staleness=3, value_bound=0.05))
    return TrainConfig(arch="olmo-1b", steps=steps, lr=3e-4, warmup_steps=0,
                       log_every=1, seed=seed, consistency=spec)


def _check_sync_pattern(tcfg, synced) -> None:
    spec = tcfg.consistency
    if spec.model == "bsp":
        require(all(synced), f"BSP skipped a sync: {synced}")
        return
    run = 0
    for s in synced:
        run = 0 if s else run + 1
        require(run <= spec.staleness,
                f"{spec.model}: {run} steps without a sync > s="
                f"{spec.staleness}: {synced}")


def _memory(dev) -> str:
    stats = dev.memory_stats()
    if not stats:
        return f"{dev}: memory_stats not reported"
    return (f"{dev}: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def forward_parity(cfg, seq: int, seed: int = 0) -> None:
    """Initial loss of one short sequence: default device vs host CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    from repro.models.common import ShardCtx, instantiate_tree

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(seed))
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (1, seq + 1)).astype(np.int32)
    loss = jax.jit(lambda p, i, lab: M.lm_loss(cfg, ShardCtx(), p, i, lab,
                                               remat=False)[0])
    host = float(loss(params, jax.device_put(ids[:, :-1], cpu),
                      jax.device_put(ids[:, 1:], cpu)))
    dev = jax.devices()[0]
    on_dev = jax.device_put(params, dev)
    chip = float(loss(on_dev, jax.device_put(ids[:, :-1], dev),
                      jax.device_put(ids[:, 1:], dev)))
    del on_dev
    print(f"[parity] step-0 loss on {dev.platform}: {chip!r}  host cpu: "
          f"{host!r}  |diff|={abs(chip - host)!r}  tol={PARITY_TOL}")
    require(math.isfinite(chip) and math.isfinite(host),
            "non-finite step-0 loss")
    require(abs(chip - host) <= PARITY_TOL,
            f"step-0 loss differs from the host CPU by {abs(chip - host)}")


def train_phase(cfg, batch: int = BATCH, seq: int = SEQ, steps: int = STEPS,
                parity_seq: int = PARITY_SEQ, seed: int = 0) -> None:
    import jax

    from repro.launch import train

    forward_parity(cfg, parity_seq, seed)
    dev = jax.devices()[0]
    for policy in ("bsp", "cvap"):
        tcfg = _train_config(policy, steps, seed)
        state, hist = train.run(
            tcfg, cfg, mesh=None, batch_size=batch, seq_len=seq,
            log=lambda line, p=policy: print(f"[train {p}] {line}"))
        del state
        losses = [h["loss"] for h in hist]
        gnorms = [h["grad_norm"] for h in hist]
        synced = [int(h["synced"]) for h in hist]
        require(len(hist) == steps, f"{policy}: {len(hist)} logged steps")
        require(all(map(math.isfinite, losses + gnorms)),
                f"{policy}: non-finite loss or grad_norm {losses} {gnorms}")
        _check_sync_pattern(tcfg, synced)
        steady = sorted(h["step_s"] for h in hist[1:])
        print(f"[train {policy}] layers={cfg.n_layers} batch={batch} "
              f"seq={seq} compile_s={hist[0]['compile_s']!r} "
              f"first_step_s={hist[0]['step_s']!r} "
              f"steady_step_s_median={steady[len(steady) // 2]!r} "
              f"synced={synced}")
        print(f"[train {policy}] {_memory(dev)}")


def _zipf_sampler(n: int, seed: int):
    import numpy as np
    cdf = np.cumsum(1.0 / np.arange(1, n + 1))
    cdf /= cdf[-1]
    # word ids by frequency rank, scattered over the table as a vocabulary
    # would be (alphabetical ids, not frequency order)
    perm = np.random.default_rng(seed).permutation(n)
    return lambda rng, k: perm[np.minimum(np.searchsorted(cdf, rng.random(k)),
                                          n - 1)]


def ps_phase(rows: int = PS_ROWS, topics: int = PS_TOPICS,
             tokens: int = PS_TOKENS, clocks: int = PS_CLOCKS,
             workers: int = PS_WORKERS, shards: int = PS_SHARDS,
             seed: int = 0) -> None:
    import numpy as np

    from repro.core.policies import ssp
    from repro.runtime import PSRuntime, RuntimeConfig

    rng = np.random.default_rng(seed)
    x0 = rng.integers(0, 8, (rows, topics)).astype(np.float32)
    words = _zipf_sampler(rows, seed)
    total = np.zeros((rows, topics), np.float64)
    lock = threading.Lock()

    def update_fn(w, clock, view, wrng):
        """LDA-style resampling: each token moves from its old topic to a
        new one, drawn half the time from the worker's (stale) view of the
        word's counts — integer deltas, exact in f32 in any order."""
        counts = view.get("word_topic")
        wd = words(wrng, tokens)
        old = wrng.integers(0, topics, tokens)
        new = np.where(wrng.random(tokens) < 0.5, counts[wd].argmax(1),
                       wrng.integers(0, topics, tokens))
        delta = np.zeros((rows, topics), np.float32)
        np.add.at(delta, (wd, old), -1.0)
        np.add.at(delta, (wd, new), 1.0)
        with lock:
            total[...] += delta
        return {"word_topic": delta}

    rt = PSRuntime(RuntimeConfig(workers, ssp(2), {"word_topic": x0},
                                 n_shards=shards, transport="queue",
                                 ps_kernels=True, seed=seed))
    t0 = time.perf_counter()
    stats = rt.run(update_fn, clocks, timeout=900.0)
    wall = time.perf_counter() - t0
    master = rt.master_value("word_topic")
    expected = (x0.astype(np.float64) + total).astype(np.float32)
    shard_m = rt.metrics().shards
    kernel_batches = sum(s.kernel_applies for s in shard_m)
    rows_applied = sum(s.rows_applied for s in shard_m)
    print(f"[ps] table={rows}x{topics} f32 workers={workers} shards={shards} "
          f"clocks={clocks} updates={stats.n_updates} "
          f"rows_applied={rows_applied} device_batches={kernel_batches} "
          f"violations={len(stats.violations)} wall_s={wall!r}")
    require(master.dtype == np.float32, f"master dtype {master.dtype}")
    require(np.array_equal(master, expected),
            f"final master != x0 + sum(updates) at "
            f"{int((master != expected).sum())} entries")
    require(not stats.violations, f"violations: {stats.violations[:3]}")
    require(kernel_batches > 0, "no batch went through kernels/ps_apply")
    print("[ps] final master == x0 + sum(updates) bitwise")


def _replica_spread(params) -> float:
    """max over leaves of max |replica - replica 0| (dp axis is leading)."""
    import jax
    import jax.numpy as jnp
    spread = jax.jit(lambda x: jnp.max(jnp.abs(x - x[0:1])))
    return max(float(spread(x)) for x in jax.tree.leaves(params))


def four_chip_phase(cfg, global_batch: int = FOUR_CHIP_BATCH, seq: int = SEQ,
                    steps: int = STEPS, seed: int = 0) -> None:
    import jax

    from repro.core import policies
    from repro.core.sync import vap_invariant_ok
    from repro.launch import mesh as mesh_lib
    from repro.launch import train

    devs = jax.devices()
    n = len(devs)
    mesh = mesh_lib.make_mesh((n, 1), ("data", "model"))
    require(global_batch % n == 0, f"batch {global_batch} over {n} replicas")
    first = {}
    for policy in ("bsp", "cvap"):
        tcfg = _train_config(policy, steps, seed)
        state, hist = train.run(
            tcfg, cfg, mesh=mesh, batch_size=global_batch, seq_len=seq,
            log=lambda line, p=policy: print(f"[dp{n} {p}] {line}"))
        losses = [h["loss"] for h in hist]
        synced = [int(h["synced"]) for h in hist]
        require(all(map(math.isfinite, losses)), f"non-finite {losses}")
        _check_sync_pattern(tcfg, synced)
        first[policy] = losses[0]
        steady = sorted(h["step_s"] for h in hist[1:])
        print(f"[dp{n} {policy}] compile_s={hist[0]['compile_s']!r} "
              f"steady_step_s_median={steady[len(steady) // 2]!r} "
              f"synced={synced}")
        if policy == "bsp":
            for d in devs:
                print(f"[dp{n} bsp] {_memory(d)}")
            spread = _replica_spread(state.params)
            print(f"[dp{n} bsp] replica spread after {steps} steps: "
                  f"{spread!r} (tol {REPLICA_TOL})")
            require(spread <= REPLICA_TOL, f"BSP replicas diverged: {spread}")
        else:
            pol = policies.from_spec(tcfg.consistency)
            ok = [bool(vap_invariant_ok(
                pol, jax.tree.map(lambda x, r=r: x[r], state.sync)))
                for r in range(n)]
            print(f"[dp{n} cvap] vap_invariant_ok per replica: {ok}")
            require(all(ok), "VAP invariant violated")
        del state
    # the dp=1 reference last, so it cannot raise device 0's peak above
    _, ref = train.run(_train_config("bsp", 1, seed), cfg, mesh=None,
                       batch_size=global_batch, seq_len=seq,
                       log=lambda line: print(f"[dp1 bsp] {line}"))
    diff = abs(first["bsp"] - ref[0]["loss"])
    print(f"[dp{n} bsp] first-step loss {first['bsp']!r} vs dp=1 "
          f"{ref[0]['loss']!r}: |diff|={diff!r} (tol {DP_PARITY_TOL})")
    require(diff <= DP_PARITY_TOL, f"dp={n} vs dp=1 loss differs by {diff}")


def _keep_cpu_backend() -> None:
    """The parity check runs the host CPU backend beside the TPU."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-parallel phase")
    args = ap.parse_args(argv)
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    _keep_cpu_backend()
    from repro.kernels import pallas_mode
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform}",
              file=sys.stderr)
        return 1
    if pallas_mode() != "on":
        print(f"chip_smoke: Pallas kernels must compile for the chip, "
              f"REPRO_PALLAS gives {pallas_mode()!r}", file=sys.stderr)
        return 1
    if args.chips > len(devs):
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    print(f"[smoke] devices={devs} compile cache: {enable_compile_cache()}")
    cfg = olmo_chip_share()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(cfg)
    else:
        train_phase(cfg)
        ps_phase()
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {"platform": d0.platform,
                                             "kind": d0.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
