"""The benchmark's manifest and the files the harness finds by name."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as R  # noqa: E402
from benchtiny import HELD, held_cell  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


M = manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_manifest_keys_and_names():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    for p in M["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)), p
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_end_to_end_metrics_and_bounds():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for w in CELLS:
        reported = [n for n, m in e2e.items()
                    if w in m.get("workloads", CELLS)]
        assert "setup_s" in reported and len(reported) >= 2, w


def test_per_layer_metrics_name_cells_that_report_what_they_move():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in CELLS
            assert w in moved.get("workloads", CELLS)


def _cell_files(c):
    """The files the harness finds by name for cell ``c``, each loaded as
    the harness loads it."""
    driver = R.load_module(os.path.join(ROOT, "bench", c.kind + "_cell.py"),
                           "bench_" + c.kind + "_cell")
    assert callable(driver.run)
    assert R.generator(c).__file__.endswith(
        os.path.join("traffic", c.traffic["generator"] + ".py"))
    assert R.reference(c).__file__.endswith(
        os.path.join("reference", c.config["reference"] + ".py"))
    assert c.config["kind"] == c.traffic["kind"]
    assert "setup_s" in {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2
    for v in c.limits.values():
        assert isinstance(v, (int, float)) and v >= 0


@pytest.mark.parametrize("cell", CELLS)
def test_harness_finds_every_file_of_a_cell(cell):
    c = R.find_cell(cell)
    _cell_files(c)
    assert c.limits and c.per_layer
    for m in c.per_layer:
        assert callable(R.reader(m["name"]))


@pytest.mark.parametrize("cell", sorted(HELD))
def test_held_cells_have_every_file(cell):
    assert cell not in CELLS
    _cell_files(held_cell(cell))


def test_every_cell_gets_per_layer_metrics():
    metrics = os.listdir(os.path.join(ROOT, "bench", "metrics"))
    for m in M["per_layer"]:
        assert m["name"] + ".py" in metrics, m["name"]
        assert m["layer"] and "\n" not in m["layer"]
    layers = {m["layer"] for m in M["per_layer"]}
    assert len(layers) == len({x.lower() for x in layers})
    for w in CELLS:
        c = R.find_cell(w)
        moved = {m["name"] for m in c.end_to_end}
        assert c.per_layer, w
        assert all(m["moves"] in moved for m in c.per_layer), w


def test_unknown_cell_and_unknown_device_kind_are_refused():
    with pytest.raises(R.SetupError, match="no workload"):
        R.find_cell("no-such-cell")
    with pytest.raises(R.SetupError, match="not in bench/peaks.json"):
        R.peaks_of("TPU v0 imaginary")
    v5e = R.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_run_key_uses_every_bit_of_the_seed():
    import jax
    a, b = R.run_key(5), R.run_key(2 ** 40 + 5)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()


def _bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    p = _bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_to_run_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in M["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
