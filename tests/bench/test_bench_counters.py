"""Step counters for per-layer readers (``bench/train_cell.py``): a reader
module that declares ``COUNTERS`` gets, in ``run.data["counters"]``, the
value of each of those scalars that each window step returned, fetched
with the loss in one ``jax.device_get``; a counter the step does not
return stops the run in set-up; with none declared the window reads the
loss alone, as it always has.  Driven through the whole harness on the
tiny OLMo cell, on the CPU."""
import types

import jax
import pytest

import benchtiny
from benchtiny import run_tiny

CELL = "olmo-1b-4l.cvap3"
PROBE = {"name": "probe", "unit": "1", "better": "higher",
         "source": "program_counter", "layer": "probe", "moves":
         "tokens_per_s", "workloads": [CELL]}


class KeyLog(dict):
    """A step's metrics that log which of them the harness reads."""

    def __init__(self, metrics):
        super().__init__(metrics)
        self.read = []

    def __getitem__(self, key):
        self.read.append(key)
        return super().__getitem__(key)


def harness(monkeypatch, counters):
    """Run the tiny cell with a probe reader that declares ``counters``
    (none: no ``COUNTERS`` at all); return (the run, each step's metrics
    as the step returned them, the ``jax.device_get`` calls made while the
    window was open)."""
    from bench import run as R
    from repro.launch import steps

    probe = types.SimpleNamespace(read=lambda run: None)
    if counters is not None:
        probe.COUNTERS = counters
    reader_module = R.reader_module
    monkeypatch.setattr(R, "reader_module", lambda m: (
        probe if m == "probe" else reader_module(m)))
    tiny_cell = benchtiny.tiny_cell

    def with_probe(name):
        cell = tiny_cell(name)
        cell.per_layer = cell.per_layer + [PROBE]
        return cell
    monkeypatch.setattr(benchtiny, "tiny_cell", with_probe)

    returned = []
    make = steps.make_train_step

    def logged(cfg, tcfg, mesh, donate=True, unroll=False):
        real = make(cfg, tcfg, mesh, donate=donate, unroll=unroll)

        def step(state, batch):
            state, m = real(state, batch)
            returned.append(KeyLog(m))
            return state, returned[-1]
        return step
    monkeypatch.setattr(steps, "make_train_step", logged)

    in_window, fetches = [False], []
    device_get = jax.device_get

    def counted_get(x):
        if in_window[0]:
            fetches.append(x)
        return device_get(x)
    monkeypatch.setattr(jax, "device_get", counted_get)
    mark = R.Run.mark_setup_done

    def window_opens(run):
        in_window[0] = True
        mark(run)
    monkeypatch.setattr(R.Run, "mark_setup_done", window_opens)
    memory_peak = R.memory_peak

    def window_closed(devices):
        in_window[0] = False
        return memory_peak(devices)
    monkeypatch.setattr(R, "memory_peak", window_closed)

    runs = []
    line = R.result_line
    monkeypatch.setattr(R, "result_line", lambda run, correct: (
        runs.append(run), line(run, correct))[1])
    out = run_tiny(CELL)
    assert out["correct"], out["checks"]
    return runs[0], returned, fetches


def test_a_reader_gets_each_window_step_s_counters(monkeypatch):
    run, returned, fetches = harness(monkeypatch, ("synced", "grad_norm"))
    checked = run.cell.traffic["checked_steps"]
    n = run.data["steps"]
    assert n > 0 and len(returned) == checked + n
    window = returned[checked:]
    got = run.data["counters"]
    assert set(got) == {"synced", "grad_norm"}
    for c in got:
        assert got[c] == [float(m[c]) for m in window], c
    assert len(set(got["grad_norm"])) > 1
    # one fetch a step, of the loss and the counters together
    assert len(fetches) == n
    assert all(set(f) == {"loss", "synced", "grad_norm"} for f in fetches)


@pytest.mark.parametrize("counters", [("moe_routed_rows",),
                                      ("grad_norm", "moe_max_expert_rows")])
def test_a_counter_the_step_does_not_return_stops_set_up(monkeypatch,
                                                         counters):
    from bench import run as R
    monkeypatch.setattr(R.Run, "mark_setup_done",
                        lambda run: pytest.fail("the window opened"))
    with pytest.raises(R.SetupError, match=counters[-1]) as e:
        harness(monkeypatch, counters)
    assert "grad_norm" not in str(e.value)


@pytest.mark.parametrize("counters", [None, ()])
def test_without_counters_the_window_reads_only_the_loss(monkeypatch,
                                                         counters):
    run, returned, fetches = harness(monkeypatch, counters)
    checked = run.cell.traffic["checked_steps"]
    assert "counters" not in run.data
    assert fetches == []
    assert [m.read for m in returned[checked:]] == (
        [["loss"]] * run.data["steps"])
