"""CPU smoke runs of the mesh fit cell: its driver on 4 host devices at a
tiny size, its readers on a small reduced trace, and its control.

The program's plain-XLA kernels run here (``impl="chunked"``); nothing
below measures time.
"""

import os
import sys
from pathlib import Path

# The mesh needs 4 devices: force host devices before JAX's backend
# starts, as tests/conftest.py does for the whole suite.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import control, control_mesh, flops, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

CELL = "gpc-mnist-mesh.fit"
N = 1024


def small_cell():
    cell = harness.Cell(CELL)
    cell.config = dict(cell.config, n=N)
    return cell


@pytest.fixture(scope="module")
def driven():
    cell = small_cell()
    driver = cell.driver()
    work = driver.make(cell.config, cell.traffic, 2**31 + 13, impl="chunked")
    orders = list(work.orders)
    work.warm_up()
    record = work.window(0.0)
    readings = work.check(work.host_records())
    return cell, driver, orders, record, readings


def test_mesh_driver_places_rows_once_and_fits(driven):
    cell, driver, orders, record, readings = driven
    chips = cell.traffic["mesh_devices"]
    assert cell.chips == chips == 4
    for x, y in orders:
        assert len({s.device for s in x.addressable_shards}) == chips
        assert len({s.device for s in y.addressable_shards}) == chips
    assert len(record["fits"]) == 1
    attempted, failed = driver.attempted_failed(record)
    assert attempted == len(record["fits"][0]["matvecs"]) >= 2
    assert failed == 0 and readings["unsolved"] == 0
    assert set(driver.end_to_end(record)) == {"fit_s"}
    assert set(readings) == {"gram_err", "solve_gap", "unsolved"}
    # Without a trace only the metrics read from the record are there.
    run = bench_run.Run(cell, record, None, flops.peaks("TPU v5 lite"))
    assert set(bench_run.per_layer(run)) == {
        "newton.host_share.fit", "engine.matvecs_per_fit"}


def _trace(chips):
    """A reduced trace of a 10 s window: per device 8 s of Gram kernel in
    f32[16384,8] events of 70 ms, 0.3 s of collectives."""
    per_event = 0.07
    events = [{"seconds": per_event, "shape": "f32[256,8]"}] * 40 * chips
    return {
        "window_s": 10.0, "busy_s": 8.5, "devices": chips,
        "device_ops": {"rbf_gram_matvec": 2.8 * chips,
                       "all-gather-done": 0.2 * chips,
                       "all-reduce": 0.1 * chips, "fusion": 0.4 * chips},
        "idle_gaps": {}, "spans": [], "events": {"rbf_gram_matvec": events},
    }


def test_mesh_readers_divide_by_the_chips(driven):
    cell, _, _, record, _ = driven
    peak = flops.peaks("TPU v5 lite")
    run = bench_run.Run(cell, record, _trace(4), peak)
    metrics = {m: v["value"] for m, v in bench_run.per_layer(run).items()}
    # Collectives: 0.3 s a device over a 10 s window.
    assert metrics["sharded.collective_share.fit"] == pytest.approx(0.03)
    # A chip's event is a quarter of a square pass: its bound is a
    # quarter of the square pass's.
    bound = flops.gram_bound_s(N, cell.config["d"], 1, peak) / 4
    assert metrics["rbf_gram_matvec_roofline.mesh"] == pytest.approx(
        100.0 * bound / 0.07)
    from bench.readers import fit_flops

    assert metrics["mfu.fit_mesh"] == pytest.approx(
        100.0 * fit_flops(run) / (10.0 * 4 * peak["bf16_flops_per_s"]))
    # The rows were placed at set-up: placing them in a fit took a sliver.
    assert 0.0 <= metrics["laplace.place_share.fit"] < 0.05
    assert metrics["laplace.syncs_per_system.fit"] == 2.0
    assert all(v > 0 for k, v in metrics.items() if k != "laplace.place_share.fit")


def test_control_reaches_the_sharded_gram_passes():
    """With the ``HIGH`` product in the rectangular kernel's place, the
    mesh fit's Gram products read far above the program's."""
    cell = small_cell()
    with control_mesh.control_in_place():
        program = control.readings(cell, 21, 0.0, "chunked")
        ctl = control.readings(cell, 21, 0.0, control.CONTROL)
    assert ctl["gram_err"] > 20 * program["gram_err"]
