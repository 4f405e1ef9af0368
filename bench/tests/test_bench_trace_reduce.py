"""The reduction from a profiler trace to busy, idle, op time and gaps."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns


def _ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS, stats=[])


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 100),
        _ev("bench.fit", 6, 89),
        _ev("bench.solve", 10, 5),
        _ev("bench.newton_step", 60, 10),
        _ev("not_ours", 62, 2),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            _ev("%while.3 = (f32[8]) while(f32[8] %x)", 10, 35),
            _ev("%rbf_gram_matvec.1 = f32[512,8]{1,0} custom-call(f32[512])", 10, 30),
            _ev("%fused_cg_update.2 = f32[512]{0} custom-call()", 40, 5),
            _ev("rbf_gram_matvec", 50, 10),
            _ev("rbf_gram_matvec", 120, 10),  # after the window
        ]),
        NS(name="XLA Modules", events=[_ev("jit_solve", 10, 50)]),
    ])
    return [host, dev, NS(name="/device:CPU:0", lines=[])]


def test_reduce_synthetic(monkeypatch):
    monkeypatch.setattr(tr, "_load_planes", lambda path: _planes())
    red = tr.reduce_trace("unused", kernels=("rbf_gram_matvec",))
    assert red["window_s"] == pytest.approx(0.100)
    # Busy: [10, 45] and [50, 60] -> 45 ms of 100.
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["devices"] == 1
    # Own time: the while loop's body ops take all of its 35 ms.
    assert red["device_ops"] == pytest.approx(
        {"rbf_gram_matvec": 0.040, "fused_cg_update": 0.005, "while": 0.0}
    )
    events = red["events"]["rbf_gram_matvec"]
    assert [e["seconds"] for e in events] == pytest.approx([0.030, 0.010])
    assert [e["shape"] for e in events] == ["f32[512,8]", ""]
    # Gaps: [0, 10] before any span but the window -> none; [45, 50] in
    # fit; [60, 100]: midpoint 80 lies in fit (newton_step ended at 70).
    assert red["idle_gaps"] == pytest.approx({"none": 0.010, "fit": 0.045})
    spans = {s["name"]: s for s in red["spans"]}
    assert set(spans) == {"bench.fit", "bench.solve", "bench.newton_step"}
    assert spans["bench.solve"]["busy_s"] == pytest.approx(0.005)
    assert spans["bench.newton_step"]["busy_s"] == pytest.approx(0.0)


def test_innermost_span_takes_the_gap(monkeypatch):
    planes = _planes()
    planes[1].lines[0].events = [_ev("op", 0, 61), _ev("op", 69, 31)]
    monkeypatch.setattr(tr, "_load_planes", lambda path: planes)
    red = tr.reduce_trace("unused")
    assert red["idle_gaps"] == pytest.approx({"newton_step": 0.008})
    assert red["busy_s"] == pytest.approx(0.092)


def test_no_window_is_an_error(monkeypatch):
    planes = _planes()
    planes[0].lines[0].events = planes[0].lines[0].events[1:]
    monkeypatch.setattr(tr, "_load_planes", lambda path: planes)
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_trace("unused")


def test_op_names():
    assert tr.op_name("%cond.2.clone.2 = (f32[8]{0}) conditional(s32[])") == (
        "cond", "(f32[8]")
    assert tr.op_name("%copy-start.15 = f32[4]{0} copy-start(f32[4])") == (
        "copy-start", "f32[4]")
    assert tr.op_name("jit_solve") == ("jit_solve", "")


def test_interval_helpers():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert tr.covered(merged, 2, 6) == pytest.approx(2)
    assert tr._gaps(merged, 0, 10) == [(3, 5), (9, 10)]


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5 lite chip: two Newton systems of a
    1024-point GP classification (n = 1024, d = 256, block 256), each in
    benchmark spans, inside a ``bench.window`` span."""
    path = Path(__file__).with_name("data") / "small_trace.xplane.pb.gz"
    red = tr.reduce_trace(path, kernels=("rbf_gram_matvec",))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.036497077)
    assert red["busy_s"] == pytest.approx(0.001827528)
    events = red["events"]["rbf_gram_matvec"]
    assert len(events) == 46
    assert {e["shape"] for e in events} == {"f32[1024,8]"}
    assert sum(e["seconds"] for e in events) == pytest.approx(0.00125588)
    top = list(red["device_ops"].items())
    assert top[0] == ("rbf_gram_matvec", pytest.approx(0.001255498))
    assert top[1] == ("while", pytest.approx(0.000222576))
    # Own times add up to the busy time: no op is counted twice.
    assert sum(red["device_ops"].values()) == pytest.approx(red["busy_s"], rel=1e-3)
    assert red["idle_gaps"] == pytest.approx({
        "newton_system": 0.014593158, "none": 0.009447997,
        "newton_step": 0.006833153, "solve": 0.003795241,
    })
    assert sum(red["idle_gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    solves = [s for s in red["spans"] if s["name"] == "bench.solve"]
    assert [s["busy_s"] for s in solves] == pytest.approx([0.001114612, 0.000553805])
