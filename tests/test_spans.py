"""repro.runtime.spans: the program's spans and counters, and the spans
``laplace_gpc`` and ``SolveService`` record with it.

All on the CPU: nesting, the ring, the off switch, the sync counter, the
compile listener, the shared clock with the profiler's trace, and the
documented span structure of a Newton fit and of a serving tick.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DenseMatrixOperator, SolveSpec
from repro.gp import RBFKernel, laplace_gpc
from repro.kernels import ops as kops
from repro.kernels.rbf_matvec import R_VPU, SYM_OUT_BYTES
from repro.runtime import spans
from repro.serve import SolveService


def _since(marker):
    """The records appended after ``marker`` (a record in the ring)."""
    recs = spans.recent()
    ids = [r.id for r in recs]
    return recs[ids.index(marker.id) + 1:] if marker.id in ids else recs


@pytest.fixture
def mark():
    with spans.span("test.mark") as m:
        pass
    return m


def test_spans_nest_with_parents(mark):
    with spans.span("t.outer", kind="a") as outer:
        with spans.span("t.inner") as inner:
            inner.attrs["late"] = 3
        with spans.span("t.second") as second:
            pass
    assert outer.parent is None
    assert inner.parent == outer.id and second.parent == outer.id
    assert inner.attrs == {"late": 3} and outer.attrs == {"kind": "a"}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns
    assert inner.end_ns <= second.start_ns <= second.end_ns <= outer.end_ns
    assert [r.name for r in _since(mark)] == ["t.outer", "t.inner", "t.second"]


def test_ring_keeps_the_last_capacity_records(mark):
    for i in range(spans.CAPACITY + 10):
        with spans.span("t.wrap", i=i):
            pass
    recs = spans.recent()
    assert len(recs) == spans.CAPACITY
    assert [r.attrs["i"] for r in recs] == list(range(10, spans.CAPACITY + 10))


def test_disabled_spans_record_nothing(mark):
    spans.enable(False)
    try:
        with spans.span("t.off") as off:
            with spans.span("t.off_inner"):
                spans.fetch(jnp.ones(3), "t.off_wait")
        spans.interval("t.off_interval", 0, 1)
    finally:
        spans.enable(True)
    assert _since(mark) == []
    # A span still times itself, for callers that read its duration.
    assert off.end_ns >= off.start_ns > 0
    assert "syncs" not in off.attrs


def test_fetch_counts_and_times_one_wait(mark):
    x = jnp.arange(5.0) * 2.0
    with spans.span("t.outer") as outer:
        with spans.span("t.inner") as inner:
            got = spans.fetch(x, "t.wait")
        spans.block(x, "t.wait")
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, np.arange(5.0) * 2.0)
    assert inner.attrs["syncs"] == 1 and outer.attrs["syncs"] == 2
    waits = [r for r in _since(mark) if r.name == "t.wait"]
    assert [w.parent for w in waits] == [inner.id, outer.id]
    for w, around in zip(waits, (inner, outer)):
        assert "syncs" not in w.attrs
        assert around.start_ns <= w.start_ns <= w.end_ns <= around.end_ns


def test_compile_is_attributed_to_the_open_span():
    x = jnp.arange(1237.0)
    with spans.span("t.compiling") as span:
        with spans.span("t.innermost") as innermost:
            jax.jit(lambda v: v * 2.75 + 0.125)(x).block_until_ready()
    assert innermost.attrs["compiles"] >= 1
    assert innermost.attrs["compile_s"] > 0.0
    assert "compiles" not in span.attrs


@pytest.mark.parametrize("r", [1, R_VPU + 1])
def test_gram_kernel_counts_its_kv_unit_on_the_open_span(r):
    """Tracing the Gram kernel leaves which unit took ``K·v`` on the
    innermost open span; a shape no other test traces, so the kernel's
    jit cache cannot skip the trace."""
    x = jnp.ones((29, 3), jnp.float32)
    v = jnp.ones((29, r), jnp.float32)
    with spans.span("t.gram") as outer:
        with spans.span("t.gram_inner") as inner:
            kops.rbf_matvec(x, v, 1.0, 1.0, impl="interpret", block=32)
    unit, other = (("gram_kv_vpu", "gram_kv_mxu") if r <= R_VPU
                   else ("gram_kv_mxu", "gram_kv_vpu"))
    assert inner.attrs[unit] == 1 and other not in inner.attrs
    assert unit not in outer.attrs


@pytest.mark.parametrize("call", ["square", "rect", "wide", "long"])
def test_gram_kernel_counts_its_symmetric_body_on_the_open_span(call):
    """Only the square pass with r ≤ R_VPU whose output fits
    ``SYM_OUT_BYTES`` takes the symmetric body and counts ``gram_sym``;
    a rectangular call, wider V and a longer pass (traced, not run) do
    not.  Shapes no other test traces."""
    n = SYM_OUT_BYTES // (4 * 8) + 1 if call == "long" else 37
    x = jnp.ones((n, 5), jnp.float32)
    r = R_VPU + 1 if call == "wide" else 1
    v = jnp.ones((n, r), jnp.float32)
    with spans.span("t.gram_sym") as outer:
        with spans.span("t.gram_sym_inner") as inner:
            if call == "rect":
                kops.rbf_matvec_rect(x[:19], x, v, 1.0, 1.0,
                                     impl="interpret", block=32)
            else:
                jax.eval_shape(lambda x, v: kops.rbf_matvec(
                    x, v, 1.0, 1.0, impl="interpret", block=32), x, v)
    assert ("gram_kv_mxu" if call == "wide" else "gram_kv_vpu") in inner.attrs
    if call == "square":
        assert inner.attrs["gram_sym"] == 1
    else:
        assert "gram_sym" not in inner.attrs
    assert "gram_sym" not in outer.attrs


def test_spans_share_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with spans.span("t.clock_outer") as outer:
            time.sleep(0.004)
            with spans.span("t.clock_inner") as inner:
                time.sleep(0.003)
    ring = {"t.clock_outer": outer.seconds, "t.clock_inner": inner.seconds}
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"]
    found = {}
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ring:
                    found[ev.name] = ev.duration_ns * 1e-9
    assert set(found) == set(ring)
    for name, seconds in ring.items():
        assert abs(found[name] - seconds) < 50e-6, (name, found, ring)


def test_laplace_fit_spans(mark):
    rng = np.random.default_rng(3)
    n = 48
    x = jnp.asarray(rng.standard_normal((n, 3)))
    y = jnp.asarray(np.where(rng.standard_normal(n) > 0, 1.0, -1.0))
    res = laplace_gpc(
        x, y, RBFKernel(1.0, 1.5),
        spec=SolveSpec("defcg", k=4, ell=6, tol=1e-8, maxiter=200),
        impl="chunked", max_newton=8,
    )
    recs = _since(mark)
    systems_n = len(res.trace.psi)
    (fit,) = [r for r in recs if r.name == "laplace.fit"]
    systems = [r for r in recs if r.name == "laplace.system"]
    assert fit.attrs["systems"] == len(systems) == systems_n >= 2
    assert all(s.parent == fit.id for s in systems)
    for s in systems:
        kids = [r.name for r in recs if r.parent == s.id
                and r.name != "laplace.wait"]
        assert kids == ["laplace.newton_system", "laplace.solve",
                        "laplace.newton_step"]
        # The documented count: 2 reads per system (the solution and one
        # readout of the system's scalars), none per fit.
        assert s.attrs["syncs"] == 2
    assert fit.attrs["syncs"] == 2 * systems_n
    waits = [r for r in recs if r.name == "laplace.wait"]
    assert len(waits) == fit.attrs["syncs"]
    solve_s = sum(r.seconds for r in recs if r.name == "laplace.solve")
    assert solve_s == pytest.approx(res.trace.cumulative_time[-1], rel=1e-12)


def _spd(n, seed):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return jnp.asarray((q * np.linspace(1.0, 50.0, n)) @ q.T)


def test_serve_tick_spans_and_tickets(mark):
    n, tenants = 32, ("a", "b", "c")
    svc = SolveService(SolveSpec(k=4, ell=6, tol=1e-8, maxiter=500), slots=3)
    rng = np.random.default_rng(0)
    tickets = []
    for rnd in range(2):
        for i, t in enumerate(tenants):
            A = DenseMatrixOperator(_spd(n, 10 * i + rnd))
            tickets.append(svc.submit(t, A, jnp.asarray(rng.standard_normal(n))))
        svc.run_until_idle()
    served = [svc.result(t, drive=False) for t in tickets]
    # A lone tenant takes the single-slot path.
    lone = svc.submit("a", DenseMatrixOperator(_spd(n, 99)),
                      jnp.asarray(rng.standard_normal(n)))
    served.append(svc.result(lone))

    recs = _since(mark)
    ticks = {r.id: r for r in recs if r.name == "serve.tick"}
    phases = [r for r in recs if r.name in (
        "serve.admit", "serve.build_batch", "serve.pool_step",
        "serve.fetch", "serve.scatter")]
    assert phases and all(p.parent in ticks for p in phases)
    for p in phases:
        tick = ticks[p.parent]
        assert tick.start_ns <= p.start_ns <= p.end_ns <= tick.end_ns
    assert {t.attrs["serving"] for t in ticks.values()} == {1, 3}
    for tick in ticks.values():
        kids = [p.name for p in phases if p.parent == tick.id]
        if tick.attrs["serving"] > 1:
            assert kids == ["serve.admit", "serve.build_batch",
                            "serve.pool_step", "serve.fetch", "serve.fetch",
                            "serve.scatter"]
            assert tick.attrs["syncs"] == 2
        elif tick.attrs["serving"] == 1:
            assert kids == ["serve.admit", "serve.pool_step", "serve.fetch",
                            "serve.fetch", "serve.scatter"]

    records = [r for r in recs if r.name == "serve.ticket"]
    assert len(records) == len(served) == 7
    by_tick = {t.attrs["tick"]: t for t in ticks.values()}
    for rec, res in zip(records, served):
        assert rec.attrs["tick"] == res.tick
        assert rec.attrs["tick_start_ns"] == by_tick[res.tick].start_ns
        assert rec.start_ns <= rec.attrs["tick_start_ns"]
        assert rec.attrs["tick_start_ns"] <= rec.attrs["scatter_ns"] <= rec.end_ns
    waited = sum(r.attrs["tick_start_ns"] - r.start_ns for r in records) * 1e-9
    snap = svc.metrics_snapshot()["tenants"]
    assert sum(t["queue_wait_s"] for t in snap.values()) == pytest.approx(waited)
