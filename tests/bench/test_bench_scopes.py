"""Device time by the program's named scopes (bench/scopes.py), on traces
whose numbers are worked out by hand, and on one recorded on a v5e chip.

bench/testdata/scopes_small.json (times in ns, window [10, 500)):

device 0: fusion.1 [0,100) attention_core; while.1 [100,400) around
          fusion.2 [100,200) attention (recomputed), fusion.3 [200,300)
          mlp (';'-joined with an optimizer op_name), copy.1 [300,350)
          with no op_name; fusion.4 [400,450) optimizer; fusion.5
          [450,480) sync; fusion.6 [480,520) not in the map; fusion.7
          [520,600) embed, after the window
device 1: fusion.1 [0,100); fusion.8 [100,160) lm_head; fusion.9
          [160,200) embed; fusion.10 [200,230) step_metrics

bench/testdata/clock_offset.json (ms): three steps; on the host clock the
dispatch spans start at 1, 11, 21 and the loss reads end at 10.2, 20.1,
30.3; the programs run at [2.2, 10), [12.5, 20), [21.8, 30) on the host's
clock and are recorded 1 ms earlier, as a device clock 1 ms behind.
"""
import gzip
import itertools
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace, scopes  # noqa: E402
from bench import run as R  # noqa: E402

TESTDATA = os.path.join(ROOT, "bench", "testdata")
MS = 1_000_000
METRICS = ["step_ms." + s for s in scopes.SCOPES + (scopes.UNSCOPED,)]
# the scopes of OLMo's train step, which the fixtures' op names use; a
# manifest may register more
OLMO_SCOPES = ("embed", "blocks", "attention", "attention_core", "mlp",
               "lm_head", "optimizer", "sync", "step_metrics")


def step_ms_entries(manifest: str):
    """The ``X`` of the manifest's ``step_ms.X`` entries, ``unscoped``
    left out, in order."""
    with open(manifest) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    return tuple(n[len(scopes.METRIC):] for n in names
                 if n.startswith(scopes.METRIC)
                 and n != scopes.METRIC + scopes.UNSCOPED)


def manifest_with_new_scope(tmp_path):
    """A copy of ``BENCHMARK.json`` with one more ``step_ms.X`` entry, X a
    name the manifest does not register yet; (its path, X)."""
    with open(scopes.MANIFEST) as f:
        doc = json.load(f)
    new = next(n for n in (f"probe{i}" for i in itertools.count())
               if n not in step_ms_entries(scopes.MANIFEST))
    entry = next(m for m in doc["per_layer"]
                 if m["name"].startswith(scopes.METRIC))
    doc["per_layer"].append(dict(entry, name=scopes.METRIC + new))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path), new


@pytest.mark.parametrize("op_name, scope", [
    ("jit(local_step)/jvp(blocks)/while/body/closed_call/attention/"
     "attention_core/dot_general", "attention_core"),
    ("jit(local_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "checkpoint/rematted_computation/attention/reshape", "attention"),
    ("jit(local_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "checkpoint/mlp/jit(silu)/logistic", "mlp"),
    ("jit(local_step)/transpose(jvp(lm_head))/while/body", "lm_head"),
    ("jit(local_step)/transpose(jvp(blocks))", "blocks"),
    ("jit(local_step)/transpose(jvp(attention_core))/dot_general",
     "attention_core"),
    ("jit(local_step)/optimizer/mul;jit(local_step)/sync/add", "optimizer"),
    ("jit(local_step)/sync/jit(_where)/select_n", "sync"),
    ("jit(local_step)/step_metrics/reduce_sum", "step_metrics"),
    ("jit(local_step)/transpose(jvp(embed))/scatter-add", "embed"),
    ("jit(local_step)/jvp()/convert_element_type", "unscoped"),
    ("jit(local_step)/transpose(jvp(jit(_var)))/reduce_sum", "unscoped"),
    ("jit(mlp)/dot_general", "unscoped"),
    ("state.params['embed']", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """\
HloModule jit_local_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(f32[8]{0} %param_0.1, f32[8]{0} %param_0.1), metadata={op_name="jit(local_step)/optimizer/mul" source_file="/x.py" source_line=3}
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %gte), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/transpose(jvp(blocks))/while/body/closed_call/checkpoint/rematted_computation/attention/attention_core/exp;jit(local_step)/mlp/x" source_file="/x.py" source_line=9}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(s32[] %i, f32[8]{0} %fusion.7)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %copy.2 = f32[8]{0} copy(f32[8]{0} %p)
  %fusion.8 = f32[8]{0} fusion(f32[8]{0} %copy.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/sync/add" source_file="/x.py" source_line=12}
  ROOT %while.3 = f32[8]{0} while(f32[8]{0} %fusion.8), condition=%cond.1, body=%body.2
}
"""


def test_op_names_from_hlo_text():
    m = scopes.op_names_from_hlo(HLO)
    assert m["fusion.7"].startswith("jit(local_step)/transpose(jvp(blocks))")
    assert {k: scopes.scope_of(v) for k, v in m.items()} == {
        "param_0.1": "unscoped", "multiply.3": "optimizer",
        "arg": "unscoped", "fusion.7": "attention_core",
        "tuple.1": "unscoped", "p": "unscoped", "copy.2": "unscoped",
        "fusion.8": "sync", "while.3": "unscoped"}


@pytest.fixture(scope="module")
def small():
    return scopes.load_json(os.path.join(TESTDATA, "scopes_small.json"))


def test_scope_times_by_hand(small):
    trace, _, op_names = small
    window = trace.window()
    assert window == (10, 500)
    st = scopes.scope_times(trace, window, op_names, steps=2)
    # device 0: attention_core 90 (clipped), attention 100, mlp 100,
    # copy.1 50 + unmapped fusion.6 20 (clipped), optimizer 50, sync 30;
    # device 1: attention_core 90, lm_head 60, embed 40, step_metrics 30
    want = {"embed": 20, "blocks": 0, "attention": 50,
            "attention_core": 90, "mlp": 50, "lm_head": 30,
            "optimizer": 25, "sync": 15, "step_metrics": 15,
            "unscoped": 35}
    assert st.ns == {**dict.fromkeys(st.ns, 0), **want}
    assert st.unmapped_ns == 10
    assert st.leaf_ns == 330
    assert sum(st.ns.values()) == st.leaf_ns
    assert st.ms_per_step("attention_core") == pytest.approx(90 / 1e6 / 2)
    assert st.scoped


def test_scope_times_sum_to_the_leaf_op_time_in_any_window(small):
    trace, _, op_names = small
    for window in [(0, 600), (150, 460), (505, 510)]:
        for devs in (["0"], ["1"], None):
            st = scopes.scope_times(trace, window, op_names, 1, devs)
            s = devtrace.summarize(trace, window, devs)
            assert sum(st.ns.values()) == pytest.approx(st.leaf_ns)
            assert st.leaf_ns == pytest.approx(sum(s.op_ns.values()))


def test_a_program_without_scopes_reads_nothing(small):
    trace, _, op_names = small
    bare = {k: "jit(local_step)/dot_general" for k in op_names}
    st = scopes.scope_times(trace, trace.window(), bare, 2)
    assert not st.scoped
    assert st.ns["unscoped"] == st.leaf_ns == 330
    assert not scopes.scope_times(trace, trace.window(), {}, 2).scoped


@pytest.fixture(scope="module")
def clock():
    return scopes.load_json(os.path.join(TESTDATA, "clock_offset.json"))


def test_clock_bracket_holds_the_planted_offset(clock):
    trace, modules, _ = clock
    low, high, n = scopes.clock_bracket(modules["0"], trace.host)
    # low: the largest program end - loss-read end, 29.0 - 30.3 at step 1
    # is -1.3, 9.0 - 10.2 = -1.2, 19.0 - 20.1 = -1.1; high: the smallest
    # program start - dispatch start, 20.8 - 21 = -0.2
    assert n == 3
    assert low == pytest.approx(-1.1 * MS)
    assert high == pytest.approx(-0.2 * MS)
    assert low <= -1 * MS <= high


def test_clock_bracket_needs_paired_runs(clock):
    trace, modules, _ = clock
    assert scopes.clock_bracket(modules["0"][:2], trace.host) is None
    assert scopes.clock_bracket([], trace.host) is None


def test_idle_gaps_relabelled_by_the_offset(clock):
    trace, _, _ = clock
    holes = devtrace.holes(trace.devices["0"], trace.window())
    # on the device's clock: [0,1.2) window, [9,11.5) window, [19,20.8)
    # read_loss, [29,31) read_loss
    assert [devtrace.label(h, trace.host) for h in holes] == [
        "bench.window", "bench.window", "bench.read_loss",
        "bench.read_loss"]
    assert scopes.relabelled_gaps(holes, trace.host, 0) == 0
    # moved 1.1 ms later: dispatch, dispatch, dispatch, no span
    assert scopes.relabelled_gaps(holes, trace.host, int(-1.1 * MS)) == 4
    # moved 0.2 ms later only the third gap leaves its loss read
    assert scopes.relabelled_gaps(holes, trace.host, int(-0.2 * MS)) == 1


def fake_run(**data):
    cell = R.Cell("hand", 1, {}, {}, {}, [], [])
    run = R.Run(cell, seed=0, seconds=10.0, trace=True)
    run.data.update(data)
    return run


def test_readers_on_a_hand_trace(small, monkeypatch):
    trace, _, op_names = small
    monkeypatch.setattr(devtrace, "find_xplane", lambda d: d + "/x.pb")
    monkeypatch.setattr(scopes, "op_names_from_xplane", lambda p: op_names)
    monkeypatch.setattr(scopes, "modules_from_xplane", lambda p: {})
    run = fake_run(trace=trace, trace_window=trace.window(), steps=2,
                   trace_dir="t")
    got = {m: R.reader(m)(run) for m in METRICS}
    assert got["step_ms.attention_core"] == pytest.approx(90 / 2e6)
    assert got["step_ms.unscoped"] == pytest.approx(35 / 2e6)
    assert got["step_ms.blocks"] == 0
    assert sum(got.values()) == pytest.approx(330 / 2e6)


def test_readers_find_nothing_without_a_trace_or_scopes(small, monkeypatch):
    for m in METRICS:
        assert R.reader(m)(fake_run()) is None
    trace, _, op_names = small
    monkeypatch.setattr(devtrace, "find_xplane", lambda d: d + "/x.pb")
    monkeypatch.setattr(scopes, "op_names_from_xplane",
                        lambda p: {k: "jit(f)/add" for k in op_names})
    monkeypatch.setattr(scopes, "modules_from_xplane", lambda p: {})
    run = fake_run(trace=trace, trace_window=trace.window(), steps=2,
                   trace_dir="t")
    for m in METRICS:
        assert R.reader(m)(run) is None


def _recorded(tmp_path):
    """The recorded trace, unpacked where the harness looks for one."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(TESTDATA, "tiny_scoped_step.xplane.pb.gz"),
                   "rb") as f:
        (d / "tiny.xplane.pb").write_bytes(f.read())
    return str(tmp_path)


def test_recorded_v5e_scoped_step(tmp_path, capsys):
    """Three steps of the shrunk train step (record_scoped_step.py) on one
    v5e chip: the trace's own metadata maps every op, and agrees with the
    compiled program's HLO text on each op's ``op_name``."""
    tdir = _recorded(tmp_path)
    path = devtrace.find_xplane(tdir)
    trace = devtrace.load_xplane(path)
    window = trace.window()
    from_trace = scopes.op_names_from_xplane(path)
    with gzip.open(os.path.join(TESTDATA, "tiny_scoped_step.hlo.txt.gz"),
                   "rt") as f:
        from_hlo = scopes.op_names_from_hlo(f.read())
    st = scopes.scope_times(trace, window, from_trace, 3)
    assert st.scoped and st.leaf_ns > 0
    assert st.unmapped_ns <= 0.01 * st.leaf_ns
    # Adam and the gradient norm fuse into the sync scope's per-tensor
    # passes on the TPU, so optimizer and step_metrics may read 0
    for s in ("embed", "blocks", "attention", "attention_core", "mlp",
              "lm_head", "sync"):
        assert st.ns[s] > 0, s
    # where the HLO text names an op that ran, the trace names it alike;
    # the trace also names an op of a loop body that has no op_name by
    # its loop's
    ran = {o.name for o in devtrace.leaves(trace.devices["0"])}
    named = [n for n in ran if from_hlo.get(n)]
    assert len(named) >= 0.5 * len(ran)
    for name in named:
        assert from_trace[name] == from_hlo[name], name
    modules = scopes.modules_from_xplane(path)
    low, high, n = scopes.clock_bracket(modules["0"], trace.host)
    assert n == 3 and low <= high
    run = fake_run(trace=trace, trace_window=window, steps=3,
                   trace_dir=tdir)
    run.devices = []
    got = {m: R.reader(m)(run) for m in METRICS}
    assert sum(got.values()) == pytest.approx(st.leaf_ns / 1e6 / 3)
    assert "clock offset from host" in capsys.readouterr().err


@pytest.mark.parametrize("manifest", ["committed", "one_more_scope"])
def test_registered_scopes_are_the_step_ms_metrics(manifest, tmp_path):
    if manifest == "committed":
        path = scopes.MANIFEST
        assert scopes.registered() == scopes.SCOPES
    else:
        path, new = manifest_with_new_scope(tmp_path)
        assert scopes.registered(path)[-1] == new
    assert scopes.registered(path) == step_ms_entries(path)
    assert set(OLMO_SCOPES) <= set(scopes.registered(path))
    assert scopes.UNSCOPED not in scopes.registered(path)


def test_a_new_step_ms_metric_registers_its_scope(tmp_path, small,
                                                 monkeypatch):
    path, new = manifest_with_new_scope(tmp_path)
    registered = scopes.registered(path)
    assert registered == scopes.SCOPES + (new,)
    op = ("jit(local_step)/transpose(jvp(blocks))/while/body/closed_call/"
          f"checkpoint/mlp/{new}/dot_general")
    assert scopes.scope_of(op) == "mlp"
    monkeypatch.setattr(scopes, "SCOPES", registered)
    assert scopes.scope_of(op) == new
    trace, _, op_names = small
    op_names = dict(op_names, **{"fusion.3": op})
    st = scopes.scope_times(trace, trace.window(), op_names, 2)
    # fusion.3 [200, 300) on device 0 leaves mlp for the new scope
    assert st.ns[new] == 50 and st.ns["mlp"] == 0
    assert sum(st.ns.values()) == st.leaf_ns == 330


def test_a_nested_scope_splits_its_outer_scope_on_the_recorded_trace(
        tmp_path, monkeypatch):
    """A probe scope registered by a temporary manifest and planted inside
    ``mlp`` in the op names of the recorded v5e trace: the readers of the
    other scopes read as before, the probe takes what it holds from
    ``mlp``, and the scopes still add up to the leaf-op time."""
    tdir = _recorded(tmp_path / "trace")
    path = devtrace.find_xplane(tdir)
    trace = devtrace.load_xplane(path)
    names = scopes.op_names_from_xplane(path)
    manifest, new = manifest_with_new_scope(tmp_path)
    run = fake_run(trace=trace, trace_window=trace.window(), steps=3,
                   trace_dir=tdir)
    run.devices = []
    before = scopes.of_run(run)
    # every other op named under mlp moves into the probe
    under = sorted(k for k, v in names.items()
                   if scopes.scope_of(v) == "mlp"
                   and "/mlp/" in v.split(";", 1)[0])[::2]
    nested = {k: (v.replace("/mlp/", f"/mlp/{new}/", 1) if k in under
                  else v) for k, v in names.items()}
    assert under and all(f"/mlp/{new}/" in nested[k] for k in under)
    monkeypatch.setattr(scopes, "SCOPES", scopes.registered(manifest))
    monkeypatch.setattr(scopes, "op_names_from_xplane", lambda p: nested)
    run.data.pop("step_scopes")
    after = scopes.of_run(run)
    assert after.ns[new] > 0
    assert after.ns["mlp"] + after.ns[new] == pytest.approx(
        before.ns["mlp"])
    for s in before.ns:
        if s != "mlp":
            assert after.ns[s] == before.ns[s], s
    assert sum(after.ns.values()) == pytest.approx(after.leaf_ns)
    assert after.leaf_ns == before.leaf_ns


def test_recorded_v5e_step_ms_reads_as_it_did(tmp_path):
    """Every ``step_ms.*`` on the recorded trace, as the readers gave it
    when the scopes were a fixed list."""
    tdir = _recorded(tmp_path)
    trace = devtrace.load_xplane(devtrace.find_xplane(tdir))
    run = fake_run(trace=trace, trace_window=trace.window(), steps=3,
                   trace_dir=tdir)
    run.devices = []
    got = {m: R.reader(m)(run) for m in METRICS}
    want = {
        "step_ms.embed": 0.017493333333333333,
        "step_ms.blocks": 0.015094000000000002,
        "step_ms.attention": 0.023314666666666668,
        "step_ms.attention_core": 0.41307133333333335,
        "step_ms.mlp": 0.022485333333333333,
        "step_ms.lm_head": 0.022993,
        "step_ms.optimizer": 0.0,
        "step_ms.sync": 0.011288,
        "step_ms.step_metrics": 0.0,
        "step_ms.unscoped": 0.006268333333333333,
    }
    # a scope registered later reads nothing on this OLMo step
    assert got == {**dict.fromkeys(got, 0.0), **want}
