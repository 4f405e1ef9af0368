"""CPU smoke runs of each traffic driver at a tiny size, and the entry
point's refusal of a machine without a TPU.

The drivers run the program's plain-XLA kernels here (``impl="chunked"``);
nothing below measures time.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import flops, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

FIT_KEYS = {"wall_s", "solve_s", "iterations", "matvecs", "rungs",
            "converged", "logp"}
TICKET_KEYS = {"tenant", "seq", "tick", "latency_s", "iterations",
               "matvecs", "rung", "converged", "misrouted"}


def small_cell(workload, n, **traffic):
    cell = harness.Cell(workload)
    cell.config = dict(cell.config, n=n)
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def drive(cell, seconds):
    driver = cell.driver()
    work = driver.make(cell.config, cell.traffic, 2**31 + 11, impl="chunked")
    work.warm_up()
    record = work.window(seconds)
    readings = work.check(work.host_records())
    run = bench_run.Run(cell, record, None, flops.peaks("TPU v5 lite"))
    return driver, record, readings, bench_run.per_layer(run)


@pytest.mark.parametrize("workload", ["gpc-mnist.fit", "gpc-usps.fit"])
def test_fits_driver(workload):
    cell = small_cell(workload, 256)
    driver, record, readings, layer = drive(cell, 0.0)
    assert len(record["fits"]) == 1 and FIT_KEYS <= set(record["fits"][0])
    assert record["window_s"] > 0
    assert set(driver.end_to_end(record)) == {"fit_s"}
    attempted, failed = driver.attempted_failed(record)
    assert attempted == len(record["fits"][0]["matvecs"]) and failed == 0
    assert set(readings) == {"gram_err", "solve_gap", "unsolved"}
    assert readings["unsolved"] == 0
    # Without a trace only the metrics read from the record are there.
    assert set(layer) == {"newton.host_share.fit", "engine.matvecs_per_fit"}
    assert all(m["unit"] for m in layer.values())
    json.dumps(record)


def test_serve_driver():
    cell = small_cell("gpc-usps.serve", 256, tenants=3, slots=3)
    driver, record, readings, layer = drive(cell, 0.0)
    tickets = record["tickets"]
    assert len(tickets) == 3 and all(TICKET_KEYS <= set(t) for t in tickets)
    assert {t["tenant"] for t in tickets} == {0, 1, 2}
    assert set(driver.end_to_end(record)) == {
        "serve_solves_per_s", "serve_p95_s"}
    assert set(readings) == {"gram_err", "solve_gap", "misrouted", "unsolved"}
    assert readings["misrouted"] == 0 and readings["unsolved"] == 0
    assert set(layer) == {"engine.lockstep_waste.serve"}
    json.dumps(record)


def test_run_refuses_a_cpu_backend(capsys):
    assert bench_run.main(
        ["--workload", "gpc-usps.fit", "--seed", "1", "--seconds", "1"]
    ) == 2
    out, err = capsys.readouterr()
    assert "needs 1 TPU" in err and "{" not in out
