"""Finding a cell's files by name, the chip check, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything else is found by name:

* ``bench/configs/<config>.json``: the sizes as run; beside it
  ``bench/configs/<config>.py``: the layer shapes, seeded weights and the
  plain reference of that configuration;
* ``bench/traffic/<traffic>.json``: the traffic parameters, which one
  general generator reads (``bench/lib/traffic.py``);
* ``bench/workloads/<cell>.json``: the window driver
  (``bench/drivers/<driver>.py``), the mesh, the traced span, the sample
  the check compares and the limits of the checks;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# JAX's persistent compilation cache: a fixed path inside the checkout, so
# that every run after a checkout's first finds its programs
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict              # bench/configs/<config>.json
    model: object             # bench/configs/<config>.py, loaded
    workload: Dict            # bench/traffic/<traffic>.json, then
                              # bench/workloads/<cell>.json over it
    end_to_end: List[Dict]    # BENCHMARK.json metrics this cell reports
    per_layer: List[Dict]


@dataclasses.dataclass
class Check:
    """One number compared with its limit: sound when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit   # False for NaN


@dataclasses.dataclass
class Outcome:
    """What a window driver hands back to ``run.py``."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    trace: Optional[object] = None     # lib.trace.Trace of a traced run
    work: Dict = dataclasses.field(default_factory=dict)  # for the readers
    # the same check computed by the lower-precision control in the
    # program's place; read by bench/calibrate.py, never by a run
    control: Optional[Callable[[], float]] = None


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets: the reduced trace, the work the
    driver counted in the traced window, the chips' peaks and how many
    chips ran."""
    trace: object
    work: Dict
    peaks: object
    chips: int


def use_compile_cache() -> None:
    """Keep every compiled program in ``CACHE_DIR``, however quickly it
    compiled; call before the first compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Counts the programs JAX compiles or loads from its persistent cache
    from creation on: a window should read 0."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_hits")

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._EVENTS[0]:
            self.n += 1

    def _on_event(self, event, **_):
        if event == self._EVENTS[1]:
            self.n += 1


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: str = os.path.join(ROOT,
                                                         "BENCHMARK.json"),
              bench_dir: str = BENCH) -> Cell:
    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    model = load_module(os.path.splitext(os.path.join(ROOT, conf["file"]))[0]
                        + ".py", "bench_config_" + w["config"])
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(bench_dir, "workloads", name + ".json")) as f:
        workload.update(json.load(f))
    return Cell(name=name, chips=w["chips"], config=config, model=model,
                workload=workload,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def driver(cell: Cell):
    return importlib.import_module("bench.drivers." + cell.workload["driver"])


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def chips(n: int):
    """The first ``n`` TPU chips, their kind and peaks; ``NoChip`` when JAX
    finds no TPU or fewer than ``n`` chips, ``UnknownDevice`` for a kind
    missing from the peaks table."""
    import jax

    from .peaks import peaks_for

    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        raise NoChip(f"JAX finds no TPU: {e}") from None
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} TPU chips, JAX finds "
                     f"{len(devices)}")
    devices = devices[:n]
    kind = devices[0].device_kind
    return devices, kind, peaks_for(kind)


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def result_line(cell: Cell, out: Outcome, devices, kind: str,
                trace: bool, per_layer: Dict[str, float],
                breakdown: Optional[Dict]) -> Dict:
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    values = per_layer if trace else out.end_to_end
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    if trace:
        from . import trace as tr
        device["busy_s"] = tr.busy_s(out.trace)
        device["window_s"] = out.trace.window_s
    line = {"correct": all(c.ok for c in out.checks) and bool(out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def print_result(line: Dict, checks: List[Check]) -> None:
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)

