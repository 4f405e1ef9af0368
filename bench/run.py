"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload gpc-mnist.fit --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; their files, the cell's limits and its per-layer metrics are
found by name (``bench/harness.py``).  A run:

1. refuses to start (exit 2, no result) unless JAX finds a TPU with as
   many chips as the cell asks for;
2. makes the data from ``--seed`` and warms up every shape the window
   uses, all of it counted in ``setup_s`` from the start of the process;
3. runs the window for ``--seconds``; with ``--trace 1`` under the
   profiler, for the traffic file's ``trace_seconds`` at most, so that a
   trace stays a few MB; compilations inside it are counted and printed;
4. reads the device's peak memory, frees the program's state, and
   compares what the window produced with the float64 reference;
5. prints each compared number beside its limit as the last lines of
   standard error, and one JSON object as the last line of standard
   output: the cell's end-to-end metrics with ``--trace 0``, its
   per-layer metrics with ``--trace 1``.

JAX's compilation cache is kept at a fixed path inside the checkout
(``.jax_cache``, or ``$JAX_COMPILATION_CACHE_DIR``), and the trace in a
temporary directory under ``$TMPDIR`` that the run deletes.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

KERNEL = "rbf_gram_matvec"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def seed_entropy(seed: int) -> int:
    """Any whole number as a non-negative seed for numpy's generators."""
    return seed % 2**64


def accelerator(chips: int):
    """The TPU devices of the run, or ``None`` when there are too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"bench: needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return None
    return devices[:chips]


def traced(enabled: bool, log_dir):
    import contextlib

    import jax

    if not enabled:
        return contextlib.nullcontext()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=options)


class Run:
    """What a per-layer metric reads: the window's record, the reduced
    trace, the cell and the device's peaks."""

    def __init__(self, cell, record, trace, peak):
        self.cell, self.record, self.trace, self.peak = cell, record, trace, peak
        self.config = cell.config


def per_layer(run: Run) -> dict:
    out = {}
    for m in run.cell.per_layer:
        reader = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             impl: str = "auto", peak=None):
    """Set up, run the window and compare; returns ``(result, compared)``.

    ``devices`` are the chips the run uses; ``impl`` and ``peak`` let the
    tests drive a run on the CPU.
    """
    from bench import flops

    if peak is None:
        peak = flops.peaks(devices[0].device_kind)
    compiles = harness.CompileCounter()
    driver = cell.driver()
    work = driver.make(cell.config, cell.traffic, seed_entropy(seed), impl)
    work.warm_up()
    setup_s = time.perf_counter() - T_START
    print(f"setup_s={setup_s} compiles_in_setup={compiles.count} "
          f"compile_s={compiles.seconds}", flush=True)

    before = compiles.count
    if trace:
        seconds = min(seconds, cell.traffic["trace_seconds"])
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        with traced(trace, log_dir):
            with harness.span("window"):
                record = work.window(seconds)
        print(f"compiles_in_window={compiles.count - before}", flush=True)
        stats = [d.memory_stats() or {} for d in devices]
        memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        host = work.host_records()
        reduced = None
        if trace:
            from bench.trace_reduce import find_xplane, reduce_trace

            reduced = reduce_trace(find_xplane(log_dir), kernels=(KERNEL,))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    readings = work.check(host)
    del work, host
    correct, compared = harness.compare(readings, cell.limits)
    attempted, failed = driver.attempted_failed(record)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    print(f"window: {driver.end_to_end(record)}", flush=True)
    if trace:
        if not reduced["events"].get(KERNEL):
            print(f"bench: the trace holds no {KERNEL} event: the window "
                  "did not run the kernel path", file=sys.stderr)
            result["correct"] = False
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["metrics"] = per_layer(Run(cell, record, reduced, peak))
        result["breakdown"] = {
            key: [[k, v] for k, v in list(reduced[key].items())[:10]]
            for key in ("device_ops", "idle_gaps")
        }
    else:
        metrics = dict(driver.end_to_end(record), setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {
            k: {"value": v, "unit": units[k]} for k, v in metrics.items()
            if k in units
        }
    result["device"] = device
    result["compared"] = {
        row["name"]: {"value": row["value"], "limit": row["limit"]}
        for row in compared
    }
    return result, compared


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.Cell(args.workload)
    devices = accelerator(cell.chips)
    if devices is None:
        return 2
    import jax

    jax.config.update("jax_enable_x64", False)
    harness.use_program()
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result, compared = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), devices
    )
    sys.stdout.flush()
    for row in compared:
        print(f"compared {row['name']}={row['value']} limit={row['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
