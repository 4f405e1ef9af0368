"""Plumbing shared by the benchmark's entry points.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own and is found here by the name
that ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: the traffic mix, whose ``driver`` key
  names the generator ``bench/drivers/<driver>.py`` that reads it;
* ``bench/limits/<workload>.json``: the limit of each number that decides
  ``correct`` in that cell, with the readings it was set from;
* ``bench/metrics/<metric>.py``: one per-layer metric, a ``read(run)``
  that returns a number, or ``None`` when the run has nothing to read.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_program() -> None:
    """Put the program's sources on the import path."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, workload: str, benchmark: Path = ROOT / "BENCHMARK.json"):
        bench = load_json(benchmark)
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(ROOT / configs[self.entry["config"]]["file"])
        self.traffic = load_json(BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.chips = int(self.entry["chips"])
        self.end_to_end = [
            m for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]
        ]
        self.per_layer = [
            m for m in bench["per_layer"]
            if "workloads" not in m or workload in m["workloads"]
        ]
        limits = BENCH / "limits" / f"{workload}.json"
        self.limits = load_json(limits) if limits.exists() else {}

    def driver(self) -> ModuleType:
        return load_module(BENCH / "drivers" / f"{self.traffic['driver']}.py")


def span(name: str):
    """A benchmark host span, written into the profiler's trace when one
    is being recorded (``trace_reduce`` reads names starting ``bench.``)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


class CompileCounter:
    """Compilations and their seconds, from JAX's own monitoring events.

    ``backend_compile`` events fire only when XLA compiles; a program found
    in the persistent cache or already in memory fires none.
    """

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def compare(readings: Dict[str, float], limits: Dict[str, Any]) -> Tuple[bool, List[dict]]:
    """Each number beside its limit.  ``correct`` when every limit has a
    finite number at or under it; a number without a limit, or a limit
    without a number, is not correct."""
    rows, ok = [], bool(limits)
    for name in sorted(set(readings) | set(limits)):
        value = readings.get(name)
        limit = float(limits[name]["limit"]) if name in limits else None
        ok &= limit is not None and value is not None and value <= limit
        rows.append({"name": name, "value": value, "limit": limit})
    return ok, rows


class Reservoir:
    """A uniform sample of at most ``cap`` items from a stream of unknown
    length (reservoir sampling), drawn with ``rng``."""

    def __init__(self, cap: int, rng):
        self.cap, self.rng = cap, rng
        self.items: list = []
        self.seen = 0

    def add(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.cap:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.cap:
                self.items[j] = item


@contextlib.contextmanager
def patched(module: ModuleType, **replacements):
    """Replace attributes of ``module`` for the ``with`` body, then restore."""
    saved = {k: getattr(module, k) for k in replacements}
    for k, v in replacements.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(module, k, v)
