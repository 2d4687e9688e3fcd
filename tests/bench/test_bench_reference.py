"""The plain reference's training (``bench/reference/training.py``) with
OLMo's model (``bench/reference/olmo.py``), against arrays it gave before
its training was split from the model: every loss, first-gradient norm and
change norm alike to the bit, and every reading of ``bench/control.py``.

``bench/testdata/reference_tiny.npz`` holds, for the shrunk cells
(``benchtiny``) at 2 replicas, seed 7 and 3 steps: ``olmo-1b-4l.cvap3``
through ``control.train_control`` (the float32 run, then the ``fp8``,
``frozen``, ``half_batch`` and ``no_exchange`` forms, and each form's
readings) and ``olmo-1b-4l.bsp.dp4``'s float32 run.  Its matrices carry
the names of that time, ``wq.2`` for ``layers/2/mix/wq``.
"""
import os

import numpy as np

from benchtiny import ROOT, run_devices

RECORD = os.path.join(ROOT, "bench", "testdata", "reference_tiny.npz")
FORMS = ["f32", "fp8", "frozen", "half_batch", "no_exchange"]
READINGS = ("loss_gap", "grad_norm_gap", "change_norm_gap")

RUN = """
import jax, sys
import numpy as np
from bench import control
from bench import run as R
from bench.reference import training
from benchtiny import tiny_cell

out = {}

def keep(name, form, r):
    out[f"{name}/{form}/loss"] = r["loss"]
    for part in ("grad", "change"):
        for k, v in r[part].items():
            out[f"{name}/{form}/{part}/{k}"] = v

cell = tiny_cell("olmo-1b-4l.cvap3")
cell.chips = 2
calls = []
real = training.train
def recorded(*a, **k):
    calls.append(real(*a, **k))
    return calls[-1]
training.train = recorded
readings = control.train_control(cell, seed=7, require_tpu=False)
training.train = real
for form, r in zip(%(forms)r, calls):
    keep(cell.name, form, r)
for form in %(forms)r[1:]:
    for k in %(readings)r:
        out[f"{cell.name}/{form}/reading/{k}"] = np.float64(readings[form][k])

cell = tiny_cell("olmo-1b-4l.bsp.dp4")
tr = cell.traffic
host = R.generator(cell).batches(tr, cell.config["vocab_size"],
                                 tr["batch_per_chip"] * 2,
                                 tr["checked_steps"], 7)
keep(cell.name, "f32", training.train(
    R.reference(cell), cell.config, tr["policy"], tr["lr"], R.run_key(7),
    host, replicas=2, devices=jax.devices()[:2]))
np.savez(sys.argv[1] if len(sys.argv) > 1 else %(path)r, **out)
"""


def old_name(key: str) -> str:
    """``c/f/grad/layers/2/mix/wq`` -> ``c/f/grad/wq.2``."""
    head, sep, name = key.partition("/layers/")
    if not sep:
        return key
    layer, *_, leaf = name.split("/")
    return f"{head}/{leaf}.{layer}"


def test_split_reference_gives_the_recorded_arrays(tmp_path):
    path = str(tmp_path / "now.npz")
    run_devices(RUN % {"forms": FORMS, "readings": READINGS, "path": path},
                n_devices=2)
    now, then = np.load(path), np.load(RECORD)
    got = {old_name(k): now[k] for k in now.files}
    assert sorted(got) == sorted(then.files)
    for k in then.files:
        np.testing.assert_array_equal(got[k], then[k], err_msg=k)
