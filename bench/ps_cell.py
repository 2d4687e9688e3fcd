"""PS cells: ``PSRuntime.run`` with closed-loop worker threads, the shards'
dense-block apply on the device through ``kernels/ps_apply``.

Set-up makes the initial table from the seed, compiles the apply kernel
for every block shape and padded batch size the shards can submit, starts
the runtime and lets every worker finish ``warm_clocks`` clocks.  The
window then runs for ``--seconds``; each worker clock (view read, LDA
resampling, push, SSP clock gate) is timed from one entry of the worker's
``update_fn`` to its next.  When the window closes the workers' remaining
clocks carry no update, the runtime quiesces and runs its own final
checks, and the plain reference (``bench/reference/<reference>.py``)
rebuilds the table from x0 and every logged count move.  Compared:

* ``table_mismatches``: entries of the final master that differ from the
  reference (exact: the counts are integers);
* ``violations``: consistency violations the runtime recorded;
* traced runs only, ``trace_dropped``: runtime trace events lost from a
  full ring (the per-layer shares would undercount).
"""
from __future__ import annotations

import math
import os
import threading
import time

import numpy as np

MIN_PAD = 8
SLICE_S = 5.0   # the window's rate is also logged per slice of this length


def block_rows(rows: int, shards: int):
    """Row counts of the shards' dense blocks (rows are dealt round-robin)."""
    return sorted({len(range(s, rows, shards)) for s in range(shards)})


def warm_apply(block_shapes, max_entries: int, dtype) -> int:
    """Compile the apply kernel for every padded batch size up to the one
    that holds ``max_entries`` rows, for every block shape."""
    from repro.kernels.ps_apply import ops as apply_ops
    n = 0
    for rows, cols in block_shapes:
        block = np.zeros((rows, cols), dtype)
        pad = MIN_PAD
        while True:
            idx = np.arange(pad) % rows
            apply_ops.scatter_add_inplace(block, idx,
                                          np.zeros((pad, cols), dtype))
            n += 1
            if pad >= max_entries:
                break
            pad *= 2
    return n


def host_rss() -> int:
    """This process's resident memory, in bytes (0 where unknown)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError):
        return 0


def counted(apply, log: list):
    """``apply`` that also logs, per call, its time and what
    ``bench/work.ps_apply_bytes`` needs: entries, distinct rows, columns
    and item size."""
    def counted_apply(dense, rows, delta):
        log.append((time.perf_counter(), int(rows.shape[0]),
                    int(np.unique(rows).shape[0]), int(dense.shape[1]),
                    dense.itemsize))
        return apply(dense, rows, delta)
    return counted_apply


def run(run) -> bool:
    from repro.configs import ConsistencySpec
    from repro.core.policies import from_spec
    from repro.kernels.ps_apply import ops as apply_ops
    from repro.runtime import PSRuntime, RuntimeConfig
    from repro.runtime import trace as trace_mod

    from bench.run import generator, log, memory_peak, reference

    cell, cfg, tr = run.cell, run.cell.config, run.cell.traffic
    table = cfg["table"]
    gen = generator(cell).LDATraffic(tr, cfg["rows"], cfg["topics"],
                                     run.seed)
    x0 = gen.initial_counts(run.seed)
    P = int(tr["workers"])
    warm = int(tr["warm_clocks"])
    # a part carries at most one row per token; a shard batch coalesces at
    # most one part per worker per clock of the staleness window
    window_clocks = int(tr["policy"].get("staleness", 0)) + 1
    max_entries = P * window_clocks * int(tr["tokens_per_clock"])
    n_kernels = warm_apply([(r, cfg["topics"]) for r in
                            block_rows(cfg["rows"], cfg["n_shards"])],
                           max_entries, x0.dtype)
    log(f"[{cell.name}] apply kernel warmed for {n_kernels} shapes "
        f"(batches up to {max_entries} rows)")

    apply_log = []
    if run.trace:
        real_apply = apply_ops.scatter_add_inplace
        apply_ops.scatter_add_inplace = counted(real_apply, apply_log)

    n_clocks = warm + int(math.ceil(run.seconds * tr["max_clock_rate"])) + 1
    entries = [[] for _ in range(P)]
    moves = [[] for _ in range(P)]
    stop = threading.Event()
    warmed = threading.Semaphore(0)

    def update_fn(w, clock, view, wrng):
        with run.span("bench.update_fn"):
            entries[w].append(time.perf_counter())
            if clock == warm:
                warmed.release()
            if stop.is_set():
                return {}
            counts = view.get(table)
            wd, old, new = gen.resample(wrng, counts)
            moves[w].append((wd, old, new))
            return {table: gen.dense_delta(wd, old, new)}

    trace_cfg = (trace_mod.TraceConfig(sample=1.0, capacity=1 << 20)
                 if run.trace else None)
    rt = PSRuntime(RuntimeConfig(
        P, from_spec(ConsistencySpec(**tr["policy"])), {table: x0},
        n_shards=cfg["n_shards"],
        threads_per_process=tr["threads_per_process"],
        transport=tr["transport"], ps_kernels=cfg["ps_kernels"],
        seed=run.seed, trace=trace_cfg))

    def rows_applied() -> int:
        return sum(s.rows_applied for s in rt.metrics().shards)

    try:
        with run.span("bench.runtime_run"):
            rt.start(update_fn, n_clocks, timeout=run.seconds + 300.0)
            for _ in range(P):
                if not warmed.acquire(timeout=run.seconds + 300.0):
                    raise RuntimeError(f"workers did not reach clock {warm}")
            with run.profiled():
                r0 = rows_applied()
                run.mark_setup_done()
                t_open = time.perf_counter()
                m_open = time.monotonic_ns()
                with run.span("bench.window"):
                    marks = []
                    while (left := t_open + run.seconds
                           - time.perf_counter()) > 0:
                        time.sleep(min(SLICE_S, left))
                        marks.append((time.perf_counter(), rows_applied(),
                                      host_rss()))
                    r1 = rows_applied()
                    t_close = time.perf_counter()
                    m_close = time.monotonic_ns()
                    stop.set()
            stats = rt.wait()
    finally:
        if run.trace:
            apply_ops.scatter_add_inplace = real_apply
    run.window_s = t_close - t_open
    short = [w for w in range(P) if len(entries[w]) >= n_clocks
             and entries[w][-1] < t_close]
    t_prev, r_prev = t_open, r0
    for t, r, rss in marks:
        n = sum(t_prev <= b < t for e in entries for b in e)
        log(f"[{cell.name}] slice {t_prev - t_open:.1f}-{t - t_open:.1f} s: "
            f"rows_per_s={(r - r_prev) / (t - t_prev)!r} clocks={n} "
            f"host_rss_bytes={rss}")
        t_prev, r_prev = t, r
    if short:
        raise RuntimeError(f"workers {short} ran out of clocks before the "
                           f"window closed: raise max_clock_rate")

    durations = []
    for e in entries:
        for a, b in zip(e, e[1:]):
            if a >= t_open and b <= t_close:
                durations.append(b - a)
    run.attempted, run.failed = len(durations), 0
    run.end_to_end["row_updates_per_s"] = (r1 - r0) / run.window_s
    if durations:
        run.end_to_end["clock_ms_p90"] = float(
            np.percentile(np.asarray(durations) * 1e3, 90))
    shard_m = rt.metrics().shards
    run.memory_peak_bytes = memory_peak(run.devices)
    log(f"[{cell.name}] setup_s={run.setup_s!r} window_s={run.window_s!r} "
        f"clocks_in_window={len(durations)} rows_in_window={r1 - r0} "
        f"row_updates_per_s={run.end_to_end['row_updates_per_s']!r} "
        f"clock_ms_p90={run.end_to_end.get('clock_ms_p90')!r} "
        f"kernel_batches={sum(s.kernel_applies for s in shard_m)} "
        f"memory_peak_bytes={run.memory_peak_bytes} "
        f"compiles_in_window={run.compiles_between(t_open, t_close)}")

    if run.trace:
        hub = rt._require_trace()

        def clipped(kind):
            tot = 0
            for _k, t0, dur, *_ in hub.events((kind,)):
                tot += max(0, min(t0 + dur, m_close) - max(t0, m_open))
            return tot

        in_window = [(n, d, c, it) for t, n, d, c, it in apply_log
                     if t_open <= t <= t_close]
        run.data.update(
            apply_ns=clipped(trace_mod.EV_APPLY),
            block_clock_ns=clipped(trace_mod.EV_BLOCK_CLOCK),
            runtime_window_ns=m_close - m_open,
            active_shards=sum(1 for s in shard_m if s.active),
            workers=P, applies_in_window=in_window)
        run.checks.append(("trace_dropped", float(hub.dropped()),
                           cell.limits["trace_dropped"]))

    ref = reference(cell)
    all_moves = [mv for per_worker in moves for mv in per_worker]
    expected = ref.final_table(x0, all_moves)
    master = rt.master_value(table)
    run.checks += [
        ("table_mismatches", float(ref.mismatches(master, expected)),
         cell.limits["table_mismatches"]),
        ("violations", float(len(stats.violations)),
         cell.limits["violations"]),
    ]
    return True
