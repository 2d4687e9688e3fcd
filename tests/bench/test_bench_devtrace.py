"""The trace reduction, on a small trace whose numbers are worked out by
hand (bench/testdata/trace_small.json, times in ns):

device 0: fusion.1 [0,100) fusion.2 [50,150) all-reduce.1 [140,300)
          fusion.3 [280,320) while.1 [400,480) around copy.1 [400,450)
          and fusion.4 [455,470)
device 1: fusion.1 [10,60) all-reduce.1 [100,200)
host:     bench.window [0,500) bench.update_fn [0,20) and [5,30)
          bench.step_dispatch [300,380) bench.read_loss [380,420)
          bench.step_dispatch [450,500)
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace  # noqa: E402

FIXTURE = os.path.join(ROOT, "bench", "testdata", "trace_small.json")


@pytest.fixture(scope="module")
def trace():
    return devtrace.load_json(FIXTURE)


def test_window_is_the_harness_span(trace):
    assert trace.window("bench.window") == (0, 500)
    assert trace.window("bench.missing") is None


def test_leaf_ops_leave_out_loop_bodies_containers(trace):
    names = [o.name for o in devtrace.leaves(trace.devices["0"])]
    assert "while.1" not in names
    assert sorted(names) == ["all-reduce.1", "copy.1", "fusion.1",
                             "fusion.2", "fusion.3", "fusion.4"]


def test_busy_idle_and_op_totals(trace):
    s = devtrace.summarize(trace, (0, 500))
    # device 0: [0,320) + [400,480) = 400; device 1: [10,60) + [100,200)
    assert devtrace.busy_ns(trace.devices["0"], (0, 500)) == 400
    assert devtrace.busy_ns(trace.devices["1"], (0, 500)) == 150
    assert s.n_devices == 2
    assert s.busy_ns == 275
    assert s.idle_share == pytest.approx(0.45)
    assert s.op_ns == {"fusion.1": 75, "fusion.2": 50, "all-reduce.1": 130,
                       "fusion.3": 20, "copy.1": 25, "fusion.4": 7.5}
    assert s.top_ops(2) == [["all-reduce.1", 130e-9], ["fusion.1", 75e-9]]


def test_exposed_collective_time(trace):
    # device 0: [140,300) less [140,150) and [280,300) = 130;
    # device 1: [100,200) with nothing beside it = 100
    assert devtrace.exposed_collective_ns(trace.devices["0"], (0, 500)) == 130
    assert devtrace.exposed_collective_ns(trace.devices["1"], (0, 500)) == 100
    assert devtrace.summarize(trace, (0, 500)).exposed_collective_ns == 115


def test_idle_gaps_named_by_the_open_host_span(trace):
    s = devtrace.summarize(trace, (0, 500))
    assert sorted(s.gaps, key=lambda g: -g[1]) == [
        ("bench.step_dispatch", 300),     # device 1 [200,500)
        ("bench.step_dispatch", 80),      # device 0 [320,400)
        ("bench.window", 40),             # device 1 [60,100)
        ("bench.step_dispatch", 20),      # device 0 [480,500)
        ("bench.update_fn x2", 10),       # device 1 [0,10)
    ]
    assert s.top_gaps(1) == [["bench.step_dispatch", 300e-9]]


def test_window_clips_every_interval(trace):
    s = devtrace.summarize(trace, (100, 400), devices=["0"])
    # fusion.2 [100,150) all-reduce.1 [140,300) fusion.3 [280,320)
    assert s.busy_ns == 220
    assert s.op_ns == {"fusion.2": 50, "all-reduce.1": 160, "fusion.3": 40}
    assert s.exposed_collective_ns == 130
    assert s.gaps == [("bench.step_dispatch", 80)]


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 30)], [(5, 10), (15, 20)], [(0, 5), (10, 15), (20, 30)]),
])
def test_subtract(a, b, want):
    assert devtrace.subtract(a, b) == want


@pytest.mark.parametrize("text, name, collective", [
    ("%fusion.595 = (f32[8]) fusion(f32[8] %all-reduce.3), kind=kOutput",
     "fusion.595", False),
    ("%all-reduce.7 = f32[4096]{0} all-reduce(f32[4096]{0} %p)",
     "all-reduce.7", True),
    ("%all-reduce-done.2 = f32[8] all-reduce-done(f32[8] %s)",
     "all-reduce-done.2", True),
    ("%scatter_add_pallas.1 = f32[51330,128] custom-call(s32[2048] %r)",
     "scatter_add_pallas.1", False),
])
def test_op_names_from_hlo_text(text, name, collective):
    assert devtrace.short_name(text) == name
    assert devtrace.is_collective(devtrace.short_name(text)) is collective


def test_no_device_plane_is_an_error():
    empty = devtrace.Trace({}, [])
    with pytest.raises(ValueError, match="no device plane"):
        devtrace.summarize(empty, (0, 1))


RECORDED = os.path.join(ROOT, "bench", "testdata", "tiny_v5e.xplane.pb")


def test_recorded_v5e_trace():
    """A trace recorded on one v5e chip: a jitted bf16 512 x 512 matmul
    and tanh, run 3 times inside a ``bench.window`` span, each dispatch in
    a ``bench.step_dispatch`` span.  The device plane's ops come out with
    their HLO instruction names."""
    tr = devtrace.load_xplane(RECORDED)
    assert list(tr.devices) == ["0"]
    ops = tr.devices["0"]
    assert [o.name for o in ops] == ["copy-start", "copy-done",
                                     "fusion"] * 3
    assert [o.end - o.start for o in ops if o.name == "fusion"] == [
        2404, 2403, 2405]
    assert [h.name for h in tr.host] == ["bench.window"] + [
        "bench.step_dispatch"] * 3
    window = tr.window()
    s = devtrace.summarize(tr, window)
    # the device's timestamps run about 1 ms behind the host's in this
    # trace, so the window holds only the last execution's ops
    assert s.op_ns == {"copy-start": 14, "copy-done": 2, "fusion": 2405}
    assert s.busy_ns == 2421
    assert s.exposed_collective_ns == 0
