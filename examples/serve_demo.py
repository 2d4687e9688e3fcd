"""Serving demo: prefill a batch of prompts, then batched greedy decode with
ring KV caches, through the library's prefill and serve steps
(``repro.launch.steps``).

    PYTHONPATH=src python examples/serve_demo.py [--arch gemma2-2b]

With ``--ps``, serve reads from the *live parameter server* instead: workers
stream SGD-style updates through the sharded runtime under a
bounded-asynchronous policy while the main thread plays a serving client,
issuing reads through the **read-replica gateway**
(:mod:`repro.runtime.serving`) under a per-read staleness SLO and reporting
latency, measured staleness, and escalations as the table converges.

    PYTHONPATH=src python examples/serve_demo.py --ps [--policy ssp3] \
        [--replicas 2] [--slo 3]

``--slo`` is the per-read contract: an integer ``k`` means "at most ``k``
clocks behind the master's applied vector clock" (the gateway serves from
the cheapest replica whose vector clock qualifies, parks on a doorbell when
none does, and escalates to the locked master shards at the deadline);
``fresh`` sends every read to the master.  Every response is stamped with
the staleness actually measured against the live vector clock, so the
histogram printed at the end is of *observed* staleness, not requested.
``--replicas 0`` bypasses the gateway and reads the live master shards
directly (the pre-serving-tier behavior, useful as a baseline).

Running the runtime across processes
------------------------------------

``--transport`` picks where the client processes live:

* ``queue`` (default) — worker threads inside this interpreter;
* ``proc`` / ``shm`` / ``tcp`` — every client process is a real forked OS
  process; per-row updates travel as batched multi-row frames over
  shared-memory rings (``shm``, the ``proc`` default) or loopback sockets
  (``tcp``), and the GIL no longer couples workers to each other or to the
  serving tier.

The replica publish streams ride the matching serving transport (queue ->
in-process channels, proc/shm -> shm rings + doorbells, tcp -> loopback
sockets); the same frames and FIFO seq assertions as the write path.

``--trace out.json`` records the whole run with the end-to-end tracing
tier (:mod:`repro.runtime.trace`) and exports Chrome trace-event JSON on
exit — open the file in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing`` to see every layer as its own track, with update
lifelines arcing client -> shard -> replica and reads/escalations on the
gateway track.
"""
import argparse
import dataclasses
import time

import numpy as np


def run_ps_demo(args) -> None:
    from repro.core import bsp, cvap, ssp, vap
    from repro.runtime import FRESH, PSRuntime, ReadGateway, RuntimeConfig

    policy = {"bsp": bsp(), "ssp3": ssp(3), "vap": vap(0.05),
              "cvap": cvap(3, 0.05)}[args.policy]
    dim, n_workers, n_clocks = 256, args.workers, args.clocks
    rng = np.random.default_rng(0)
    A = rng.normal(0, 1, (128, dim)) / np.sqrt(dim)
    y = A @ rng.normal(0, 1, dim)

    def update_fn(w, clock, view, wrng):
        x = view.get("x")
        i = wrng.integers(0, len(y), 16)
        g = (A[i].T @ (A[i] @ x - y[i])) / len(i)
        return {"x": -0.2 * g}

    slo = args.slo if args.slo == FRESH else int(args.slo)
    serving = {"queue": "queue", "proc": "shm", "shm": "shm",
               "tcp": "tcp"}[args.transport]
    rt = PSRuntime(RuntimeConfig(n_workers, policy, {"x": np.zeros(dim)}, n_shards=2,
                   threads_per_process=1, seed=0, transport=args.transport,
                   trace=bool(args.trace) or None))
    print(f"serving from live PS runtime: {n_workers} workers, "
          f"policy {policy.kind}, {n_clocks} clocks, "
          f"transport {args.transport}, {args.replicas} replicas "
          f"({serving} publish streams), slo {slo!r}")
    rt.start(update_fn, n_clocks, timeout=300)
    gw = (ReadGateway(rt, n_replicas=args.replicas, transport=serving)
          if args.replicas > 0 else None)
    lat, stale, esc = [], [], 0
    t_next = time.perf_counter()
    while rt.running:
        t0 = time.perf_counter()
        if gw is None:
            x = rt.read("x")               # locked live master read
        else:
            res = gw.read("x", slo=slo, timeout=5.0)
            x, _ = res.value, stale.append(res.staleness)
            esc += res.escalated
        lat.append(time.perf_counter() - t0)
        if time.perf_counter() >= t_next:
            obj = float(0.5 * np.mean((A @ x - y) ** 2))
            print(f"  t+{len(lat):5d} reads  objective {obj:.5f}")
            t_next = time.perf_counter() + 0.5
        time.sleep(1e-3)
    stats = rt.wait()
    x_final = (gw.read("x", slo=0, timeout=10).value if gw is not None
               else rt.read("x"))
    q = np.quantile(np.asarray(lat), [0.5, 0.95]) if lat else [0.0, 0.0]
    obj = float(0.5 * np.mean((A @ x_final - y) ** 2))
    print(f"done: {stats.n_updates} updates in {stats.sim_time:.2f}s "
          f"({stats.n_updates / stats.sim_time:.0f} upd/s), "
          f"final objective {obj:.5f}")
    print(f"reads: {len(lat)} served, p50 {q[0]*1e6:.0f}us, "
          f"p95 {q[1]*1e6:.0f}us; violations: {len(stats.violations)}")
    if gw is not None:
        hist = np.bincount(np.asarray(stale, dtype=int) if stale else [0])
        print(f"staleness observed (clocks->reads): "
              f"{dict(enumerate(hist.tolist()))}; escalations {esc}; "
              f"per-replica {gw.stats.reads_per_replica}")
        gw.close()
    if args.trace:
        info = rt.dump_trace(args.trace)
        print(f"trace: {info['events']} events -> {info['path']} "
              f"({info['dropped']} dropped; open in Perfetto / "
              f"chrome://tracing)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ps", action="store_true",
                    help="serve reads from the live threaded PS runtime")
    ap.add_argument("--policy", default="ssp3",
                    choices=["bsp", "ssp3", "vap", "cvap"])
    ap.add_argument("--transport", default="queue",
                    choices=["queue", "proc", "shm", "tcp"],
                    help="queue = threads in-process; proc/shm/tcp = forked "
                         "client processes over the wire (see docstring)")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--clocks", type=int, default=150)
    ap.add_argument("--replicas", type=int, default=2,
                    help="read replicas behind the gateway (0 = read the "
                         "locked master shards directly, no serving tier)")
    ap.add_argument("--slo", default="3",
                    help='per-read staleness SLO: an integer k (clocks '
                         'behind the master vector clock) or "fresh"')
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the run with the end-to-end tracing tier "
                         "and export Perfetto-loadable Chrome trace JSON "
                         "here on exit (--ps mode)")
    args = ap.parse_args()
    if args.ps:
        run_ps_demo(args)
        return

    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, InputShape, reduced_config
    from repro.launch import steps
    from repro.models import model as M
    from repro.models.common import instantiate_tree

    if args.arch not in ARCHS:
        ap.error(f"unknown arch {args.arch!r} (choose from "
                 f"{', '.join(sorted(ARCHS))})")

    cfg = dataclasses.replace(reduced_config(args.arch), dtype="float32")
    print(f"serving reduced {args.arch}: {cfg.n_layers}L d{cfg.d_model}")
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))

    shape = InputShape("demo", seq_len=args.prompt_len + args.gen,
                       global_batch=args.batch, mode="prefill")
    prefill_fn = steps.make_prefill_step(cfg, None, shape)
    serve_fn = steps.make_serve_step(cfg, None, shape)

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    batch = {"ids": prompts}
    if cfg.frontend is not None:
        batch["extra_emb"] = jnp.asarray(
            rng.normal(0, 0.02, (args.batch, cfg.frontend.n_embeds,
                                 cfg.d_model)), jnp.float32)

    t0 = time.perf_counter()
    nxt, caches = prefill_fn(params, batch)
    jax.block_until_ready(nxt)
    t_prefill = time.perf_counter() - t0

    generated = [np.asarray(nxt)]
    t0 = time.perf_counter()
    for j in range(args.gen - 1):
        pos = jnp.full((args.batch,), args.prompt_len + j, jnp.int32)
        nxt, caches = serve_fn(params, caches, {"ids": nxt[:, None], "pos": pos})
        generated.append(np.asarray(nxt))
    jax.block_until_ready(nxt)
    t_decode = time.perf_counter() - t0

    gen = np.stack(generated, 1)
    print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill*1e3:.1f} ms "
          f"(incl. compile)")
    print(f"decode  {args.gen - 1} steps: {t_decode*1e3:.1f} ms "
          f"({(args.gen - 1) * args.batch / t_decode:.0f} tok/s)")
    for i in range(min(2, args.batch)):
        print(f"seq {i}: prompt …{np.asarray(prompts[i, -6:]).tolist()} -> "
              f"generated {gen[i, :10].tolist()}…")


if __name__ == "__main__":
    main()
