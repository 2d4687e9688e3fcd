#!/usr/bin/env python3
"""Controls of the correctness check: readings that the check must fail.

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--clocks N]

Each reading goes through the cell's own check: the numbers the cell
compares, each against its limit in ``bench/limits/<workload>.json`` by the
rule of ``bench/run.py`` (``passes``), and the verdict ``correct``, which a
control has to turn false.

Train cells: on the cell's checked steps and batches, the plain reference
in float32 is compared, exactly as the cell's check compares the program
(loss, first-gradient and change gaps), with the reference put in the
program's place in each of these forms:

* ``fp8``: float8 (e4m3, per-tensor scaled) matmuls, the precision below
  the configuration's bfloat16 compute (the control);
* ``frozen``: a step that leaves the state unchanged (learning rate 0);
* ``half_batch``: each replica's loss and gradient over the first half of
  its rows only;
* ``no_exchange`` (several replicas): the replicas never exchange their
  deltas.

PS cells: the cell's LDA traffic, ``--clocks`` clocks of every worker
applied in turn to exact counts, summed by the reference once in float64
and once in bfloat16, the precision below the table's float32: the
entries that differ are the cell's ``table_mismatches``.

The benchmark's own runs never run this.  It needs the cell's chips (train)
and prints one JSON line per seed, with each form's ``checks`` and
``correct``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def judged(cell, readings: dict) -> dict:
    """``readings`` with the cell's check applied: each compared number
    beside its limit, and whether the run would be correct."""
    from bench.run import passes
    checks = {k: {"value": float(readings[k]), "limit": lim}
              for k, lim in cell.limits.items() if k in readings}
    if not checks:
        raise RuntimeError(f"{cell.name}: no reading is among the limits "
                           f"{sorted(cell.limits)}")
    ok = all(passes(c["value"], c["limit"]) for c in checks.values())
    return {**readings, "checks": checks, "correct": ok}


def train_control(cell, seed: int, require_tpu: bool = True) -> dict:
    from bench import run as R
    from bench.reference import training
    from bench.train_cell import gaps
    devices, _ = R.device_check(cell.chips, require_tpu)
    tr = cell.traffic
    ref = R.reference(cell)
    checked = int(tr["checked_steps"])
    B = tr["batch_per_chip"] * cell.chips
    host = R.generator(cell).batches(tr, cell.config["vocab_size"], B,
                                     checked, seed)
    key = R.run_key(seed)
    b = tr["batch_per_chip"]
    half = np.concatenate([host[:, r * b:r * b + b // 2]
                           for r in range(cell.chips)], axis=1)
    never = {"model": "ssp", "staleness": (1 << 31) - 2}
    forms = {"f32": {}, "fp8": {"precision": "fp8"}, "frozen": {"lr": 0.0},
             "half_batch": {"batches": half}}
    if cell.chips > 1:
        forms["no_exchange"] = {"policy": never}

    def train(policy=tr["policy"], lr=tr["lr"], batches=host,
              precision="f32"):
        return training.train(ref, cell.config, policy, lr, key, batches,
                              replicas=cell.chips, precision=precision,
                              devices=devices[:cell.chips])

    lo = train()
    rec = {"seed": seed}
    for form, kw in forms.items():
        if form == "f32":
            continue
        hi = train(**kw)
        step_gaps = np.abs(hi["loss"].mean(1) - lo["loss"].mean(1))
        rec[form] = judged(cell, {
            "loss_gap": float(np.max(step_gaps)),
            "step_loss_gaps": step_gaps.tolist(),
            "grad_norm_gap": gaps(hi["grad"], lo["grad"]),
            "change_norm_gap": gaps(hi["change"], lo["change"],
                                    keep_from=lo["grad"]),
        })
    return rec


def ps_control(cell, seed: int, clocks: int) -> dict:
    import ml_dtypes
    from bench import run as R
    cfg, tr = cell.config, cell.traffic
    ref = R.reference(cell)
    gen = R.generator(cell).LDATraffic(tr, cfg["rows"], cfg["topics"], seed)
    x0 = gen.initial_counts(seed)
    counts = x0.astype(np.float64)
    moves = []
    rngs = [np.random.default_rng([seed, w]) for w in range(tr["workers"])]
    for _ in range(clocks):
        for rng in rngs:
            wd, old, new = gen.resample(rng, counts)
            np.subtract.at(counts, (wd, old), 1.0)
            np.add.at(counts, (wd, new), 1.0)
            moves.append((wd, old, new))
    exact = ref.final_table(x0, moves)
    low = ref.final_table(x0, moves, dtype=ml_dtypes.bfloat16)
    return {"seed": seed, "clocks_per_worker": clocks,
            "max_count": float(exact.max()),
            "bf16": judged(cell, {
                "table_mismatches": ref.mismatches(low, exact)})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--clocks", type=int, default=0,
                    help="PS cells: clocks of every worker")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run as R
    os.environ["JAX_COMPILATION_CACHE_DIR"] = R.COMPILE_CACHE
    cell = R.find_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.kind == "train":
            rec = train_control(cell, seed)
        else:
            rec = ps_control(cell, seed, args.clocks)
        for form, r in rec.items():
            if isinstance(r, dict):
                for k, c in r["checks"].items():
                    print(f"check {form} {k}: {c['value']} "
                          f"(limit {c['limit']})", file=sys.stderr)
                print(f"control {form} seed {seed}: correct "
                      f"{str(r['correct']).lower()}", file=sys.stderr)
        print(json.dumps({"workload": cell.name, **rec}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
