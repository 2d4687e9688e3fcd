"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo convention, plus the
full result dicts.

    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import os
import time


def _csv_line(row: dict) -> str:
    name = row.pop("name")
    us = row.pop("us_per_call", row.pop("sim_time", 0.0) * 1e6
                 if "sim_time" in row else 0.0)
    derived = ";".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in row.items())
    return f"{name},{us:.1f},{derived}"


def main() -> None:
    all_rows = []
    t0 = time.time()

    print("# --- consistency models on SGD (paper §2/§3) ---")
    from benchmarks import bench_consistency
    for r in bench_consistency.run():
        all_rows.append(dict(r))
        print(_csv_line(r))

    print("# --- LDA convergence per policy (paper §5) ---")
    from benchmarks import bench_lda
    for r in bench_lda.run():
        all_rows.append(dict(r))
        print(_csv_line(r))

    print("# --- LDA strong scaling (paper Fig. 5) ---")
    from benchmarks import bench_scalability
    for r in bench_scalability.run():
        all_rows.append(dict(r))
        print(_csv_line(r))

    print("# --- PS runtime: updates/sec + read latency per transport ---")
    from benchmarks import bench_runtime
    cal = bench_runtime.calibrate_parallelism()
    print(f"# host calibration: 2-process aggregate x{cal:.2f}")
    rt_rows = bench_runtime.run()
    for r in rt_rows:
        all_rows.append(dict(r))
        print(_csv_line(dict(r)))
    rt_out = os.path.join(os.path.dirname(__file__), "..", "results",
                          "BENCH_runtime.json")
    os.makedirs(os.path.dirname(rt_out), exist_ok=True)
    bench_runtime.write_json(rt_rows, rt_out, parallel_x2=cal)
    print(f"# wrote {rt_out}")

    print("# --- serving tier: replica reads vs locked master, per SLO ---")
    from benchmarks import bench_serving
    sv_rows = bench_serving.run()
    for r in sv_rows:
        all_rows.append(dict(r))
        print(_csv_line(dict(r)))
    sv_out = os.path.join(os.path.dirname(__file__), "..", "results",
                          "BENCH_serving.json")
    bench_serving.write_json(sv_rows, sv_out)
    print(f"# wrote {sv_out}")

    print("# --- autoscaling: rebalanced vs static layouts ---")
    from benchmarks import bench_autoscale
    as_rows = bench_autoscale.run()
    for r in as_rows:
        all_rows.append(dict(r))
        print(_csv_line(dict(r)))
    bench_autoscale.gates(as_rows)
    as_out = os.path.join(os.path.dirname(__file__), "..", "results",
                          "BENCH_autoscale.json")
    bench_autoscale.write_json(as_rows, as_out)
    print(f"# wrote {as_out}")

    print("# --- durability tier: WAL off vs group-commit vs fsync ---")
    from benchmarks import bench_wal
    wal_rows = bench_wal.run()
    for r in wal_rows:
        all_rows.append(dict(r))
        print(_csv_line(dict(r)))
    bench_wal.gates(wal_rows)
    wal_out = os.path.join(os.path.dirname(__file__), "..", "results",
                           "BENCH_wal.json")
    bench_wal.write_json(wal_rows, wal_out)
    print(f"# wrote {wal_out}")

    print("# --- convergence vs staleness per policy (SGD MF + logreg) ---")
    from benchmarks import bench_convergence
    cv_rows = bench_convergence.run()
    for r in cv_rows:
        all_rows.append(dict(r))
        slim = {k: v for k, v in r.items() if k != "curve"}
        slim.setdefault("us_per_call", 0.0)
        print(_csv_line(slim))
    bench_convergence.gates(cv_rows)
    cv_out = os.path.join(os.path.dirname(__file__), "..", "results",
                          "BENCH_convergence.json")
    bench_convergence.write_json(cv_rows, cv_out)
    print(f"# wrote {cv_out}")

    from benchmarks import common
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "bench_results.json")
    common.write_bench_json(out, "bench_results", all_rows,
                            calibration={"proc_parallel_x2": cal})
    print(f"# done in {time.time() - t0:.1f}s -> {out}")


if __name__ == "__main__":
    main()
