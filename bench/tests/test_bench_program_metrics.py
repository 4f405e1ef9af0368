"""The per-layer metrics read from the program's own spans
(``bench/program_spans.py``): each cell's traced run reports them, in
range, and a reader returns ``None`` where the ring does not hold the
window the record describes, or where the program keeps no spans.

Each case drives a whole traced run (``bench/run.py``'s ``run_cell``) on
the CPU at a small size, as ``test_bench_faults.py`` does; the numbers
are the CPU's and are checked only for their range.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import flops, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

N = 1024
SEED = 2**31 + 211
FIT = ("laplace.syncs_per_system.fit", "laplace.host_busy_share.fit")
SERVE = ("serve.tick_host_share.serve", "serve.queue_share.serve")
SMALL_SERVE = {"tenants": 3, "slots": 3}


def traced_run(workload, **traffic):
    cell = harness.Cell(workload)
    cell.config = dict(cell.config, n=N)
    cell.traffic = dict(cell.traffic, **traffic)
    result, _ = bench_run.run_cell(
        cell, SEED, 0.0, True, jax.devices()[:1], impl="chunked",
        peak=flops.peaks("TPU v5 lite"),
    )
    return result["metrics"]


def reading(workload, record):
    """The cell's span metrics read from ``record`` against the ring as
    the last run left it, as in a traced run (whose reduced trace they do
    not read)."""
    cell = harness.Cell(workload)
    run = bench_run.Run(cell, record, {}, flops.peaks("TPU v5 lite"))
    out = {}
    for name in FIT + SERVE:
        if any(m["name"] == name for m in cell.per_layer):
            mod = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
            out[name] = mod.read(run)
    return out


@pytest.mark.parametrize("workload", ["gpc-mnist.fit", "gpc-usps.fit"])
def test_fit_cells_report_span_metrics(workload):
    metrics = traced_run(workload)
    syncs = metrics["laplace.syncs_per_system.fit"]
    # 8 reads per Newton system and 2 per fit, over at least 2 systems.
    assert syncs["unit"] == "syncs" and 8.0 < syncs["value"] <= 9.0
    assert 0.0 < metrics["laplace.host_busy_share.fit"]["value"] < 1.0
    assert not set(SERVE) & set(metrics)


def test_serve_cell_reports_span_metrics():
    metrics = traced_run("gpc-usps.serve", **SMALL_SERVE)
    for name in SERVE:
        assert 0.0 < metrics[name]["value"] < 1.0, metrics
    assert not set(FIT) & set(metrics)


def test_fit_readers_refuse_a_window_the_ring_does_not_hold():
    from repro.runtime import spans

    traced_run("gpc-usps.fit")
    fits = [r for r in spans.recent() if r.name == "laplace.fit"]
    last = {"matvecs": [0] * fits[-1].attrs["systems"]}
    assert all(v is not None for v in reading("gpc-usps.fit", {"fits": [last]}).values())
    more_fits = {"fits": [last] * (spans.CAPACITY + 1)}
    other_systems = {"fits": [{"matvecs": last["matvecs"] + [0]}]}
    for record in (more_fits, other_systems):
        assert reading("gpc-usps.fit", record) == dict.fromkeys(FIT)


def test_serve_readers_refuse_a_window_the_ring_does_not_hold():
    from repro.runtime import spans

    traced_run("gpc-usps.serve", **SMALL_SERVE)
    tickets = [r for r in spans.recent() if r.name == "serve.ticket"]
    last = [{"tick": t.attrs["tick"]} for t in tickets[-3:]]
    assert all(v is not None for v in reading("gpc-usps.serve", {"tickets": last}).values())
    more_tickets = {"tickets": last * (spans.CAPACITY + 1)}
    other_ticks = {"tickets": [dict(t, tick=t["tick"] + 1) for t in last]}
    for record in (more_tickets, other_ticks):
        assert reading("gpc-usps.serve", record) == dict.fromkeys(SERVE)


def test_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    """A program from before ``repro.runtime.spans``: no number, no error."""
    harness.use_program()
    import repro.runtime
    from repro.runtime import spans

    with spans.span("laplace.fit", fit=0, systems=2):
        spans.fetch(jax.numpy.ones(2), "laplace.wait")
    record = {"fits": [{"matvecs": [1, 2]}]}
    assert reading("gpc-usps.fit", record)["laplace.syncs_per_system.fit"] == 0.5
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert reading("gpc-usps.fit", record) == dict.fromkeys(FIT)
    assert reading("gpc-usps.serve", {"tickets": [{"tick": 1}]}) == dict.fromkeys(SERVE)
