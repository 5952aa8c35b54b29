"""Resolve a cell by name, run its driver, and print its result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives:

* a cell's configuration: the ``file`` of its ``configs`` entry;
* its traffic mix: ``benchmarks/chip/traffic/<traffic>.json``, whose
  ``driver`` names the general driver ``benchmarks/chip/drivers/<driver>.py``;
* a per-layer metric: ``benchmarks/chip/metrics/<name>.py``, whose
  ``read(run)`` returns a number, or None where it finds nothing to read.

So a cell is added with files and a ``workloads`` entry, and no file
here changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

__all__ = ["Ctx", "Run", "NoChip", "load_cell", "run_cell", "result_line"]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_cell(root: Path, name: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, config, mix)`` for the cell called ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "benchmarks" / "chip" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, mix


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, name: str):
    path = root / "benchmarks" / "chip" / "metrics" / f"{name}.py"
    return _module(path, f"chip_metric_{name.replace('.', '_')}").read


def driver(name: str):
    return importlib.import_module(f"benchmarks.chip.drivers.{name}")


@dataclasses.dataclass
class Run:
    """What a driver hands back.  ``e2e`` holds the end-to-end values by
    metric name; ``checks`` each compared number with its limit;
    ``data`` whatever the cell's per-layer readers read."""

    correct: bool
    attempted: int
    failed: int
    e2e: dict
    checks: dict
    memory_peak_bytes: int
    data: dict
    trace: dict | None = None


@dataclasses.dataclass
class Ctx:
    root: Path
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    peaks: dict | None
    _tracing: bool = False

    @property
    def trace_dir(self) -> Path:
        return self.root / ".bench_trace" / self.cell["name"]

    def span(self, name: str):
        """A host span in the profiler's trace, so that idle gaps are
        named by what the host was doing."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_trace(self):
        """Open the measured window (and, with ``--trace 1``, the trace)."""
        if self.trace:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._tracing = True
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()

    def stop_trace(self):
        if self._tracing:
            import jax
            self._window.__exit__(None, None, None)
            self._tracing = False
            jax.profiler.stop_trace()

    def reduce_trace(self):
        """Read the trace once the run's work is done, then delete it."""
        if not self.trace or self.peaks is None:  # no chip: nothing traced to read
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            return None
        from . import tracereduce
        try:
            device, host = tracereduce.load(str(self.trace_dir))
            return tracereduce.reduce(device, host)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True) -> dict:
    """Run one cell once and return the result object (not yet printed)."""
    bench, cell, config, mix = load_cell(root, name)
    import jax

    devices = jax.devices()
    chips = cell["chips"]
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell {name} needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    from .peaks import peaks_for

    kind = devices[0].device_kind
    ctx = Ctx(root=root, cell=cell, config=config, mix=mix, seed=seed,
              seconds=seconds, trace=trace, t_start=t_start,
              devices=devices[:chips],
              peaks=peaks_for(kind) if require_chip else None)
    run = driver(mix["driver"]).run(ctx)

    metrics = {}
    if not trace:
        for m in bench["end_to_end"]:
            if name in m.get("workloads", [name]):
                metrics[m["name"]] = {"value": run.e2e[m["name"]], "unit": m["unit"]}
    else:
        mine = {m["name"] for m in bench["end_to_end"]
                if name in m.get("workloads", [name])}
        for m in bench["per_layer"]:
            if name in m.get("workloads", [name] if m["moves"] in mine else []):
                v = metric_reader(root, m["name"])(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": run.correct, "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in run.checks.items()}
    return out


def result_line(out: dict) -> str:
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    for m in out["metrics"].values():
        if not math.isfinite(m["value"]):
            raise ValueError(f"metric not finite: {out['metrics']}")
    return json.dumps(out)


def log(**fields) -> None:
    """A diagnostic line on stdout, before the result line."""
    print(json.dumps({"diag": fields}), flush=True)
