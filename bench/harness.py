"""What every cell shares: finding its files by name, the run record the
drivers fill, the compile counter, and the result line.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration and a
traffic mix. ``configs/<config>.json`` holds the configuration as run and
names its driver (``drivers/<driver>.py``); ``configs/<config>.py`` beside
it is its plain reference; ``traffic/<traffic>.json`` is the mix. Each
per-layer metric is read by ``metrics/<metric>.py``, whose ``read(readings)``
returns a number or ``None`` when it finds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_op(kernel: str) -> ModuleType:
    """``ops/<kernel>.py``: the kernel's operation and byte counts."""
    return load_module(BENCH / "ops" / f"{kernel}.py", f"bench_op_{kernel}")


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def config_of(spec: dict, cell_name: str, root: Path = ROOT) -> dict:
    """The configuration file of a cell, as run."""
    cell = find(spec["workloads"], cell_name, "workload")
    entry = find(spec["configs"], cell["config"], "config")
    return json.loads((root / entry["file"]).read_text())


def bind_host_cpus(n: int | None) -> list[int] | None:
    """Bind this process to ``n`` fixed cores, the lowest of those it may
    use, as a batch scheduler binds a job to the cores it allocated
    (Slurm's ``--cpus-per-task`` with ``--cpu-bind=cores``). Threads
    started later inherit the binding, so call it before JAX or the
    cluster starts any. ``None`` leaves the process unbound."""
    if not n:
        return None
    cpus = sorted(os.sched_getaffinity(0))[:int(n)]
    os.sched_setaffinity(0, cpus)
    return cpus


def metrics_for(spec: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """(end-to-end metrics, per-layer metrics) that ``cell`` reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


class CompileCounter:
    """Backend compiles and persistent-cache loads, from JAX's monitoring
    events, counted while ``counting`` is set."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.counting = False
        self.compiles = 0
        self.cache_loads = 0
        self.total = 0

    def register(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == self.COMPILE:
            self.total += 1
            if self.counting:
                self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == self.CACHE_HIT and self.counting:
            self.cache_loads += 1


@dataclass
class Run:
    """One run of one cell, filled in by its driver."""
    cell: dict
    config: dict
    reference: ModuleType
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    out_dir: Path
    compiles: CompileCounter | None = None
    setup_s: float | None = None
    e2e: dict = field(default_factory=dict)
    readings: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int | None = None
    evidence: dict = field(default_factory=dict)

    def setup_done(self) -> float:
        now = time.time()
        self.setup_s = now - self.t_start
        self.e2e["setup_s"] = self.setup_s
        return now

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return judge(self.checks)

    def correct_with(self, values: dict) -> bool:
        """Whether this run would be correct with ``values`` (a control's
        readings) in place of the program's numbers of the same names."""
        return judge({k: (float(values.get(k, v)), lim)
                      for k, (v, lim) in self.checks.items()})


def judge(checks: dict) -> bool:
    """``correct``: there is a check, and every number is finite and
    within its limit."""
    return bool(checks) and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values())


def prepare(spec: dict, cell_name: str, *, seed: int, seconds: float,
            trace: bool, t_start: float, bench: Path = BENCH,
            config_override: dict | None = None,
            mix_override: dict | None = None) -> tuple[Run, ModuleType]:
    """Resolve a cell's files into a ``Run`` and its driver module."""
    cell = find(spec["workloads"], cell_name, "workload")
    cfg_entry = find(spec["configs"], cell["config"], "config")
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    config.update(config_override or {})
    mix = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    mix.update(mix_override or {})
    ref_path = (ROOT / cfg_entry["file"]).with_suffix(".py")
    reference = load_module(ref_path, f"bench_ref_{cell['config']}")
    driver = load_module(bench / "drivers" / f"{config['driver']}.py",
                         f"bench_driver_{config['driver']}")
    out_dir = ROOT / ".bench_out" / f"{cell_name}-{seed}"
    run = Run(cell=cell, config=config, reference=reference, mix=mix,
              seed=seed, seconds=seconds, trace=trace, t_start=t_start,
              out_dir=out_dir)
    return run, driver


def read_layer_metrics(run: Run, layer: list[dict], bench: Path = BENCH
                       ) -> dict:
    out = {}
    for m in layer:
        reader = load_module(bench / "metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run.readings)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, spec: dict, devices) -> dict:
    e2e, layer = metrics_for(spec, run.cell["name"])
    if run.trace:
        metrics = read_layer_metrics(run, layer)
    else:
        metrics = {m["name"]: {"value": float(run.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in e2e if run.e2e.get(m["name"]) is not None}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    tr = run.readings.get("trace")
    if run.trace and tr is not None:
        from tracing import breakdown
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        line["breakdown"] = breakdown(tr)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in run.checks.items()}
    return line


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
