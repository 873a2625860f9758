"""One run of one cell: set-up, measured window, checks, result line.

Everything a cell needs is found by name. ``BENCHMARK.json`` names the
cell's configuration and traffic mix; the configuration's file holds its
sizes, ``bench/traffic/<traffic>.json`` holds the traffic's parameters
and the driver kind, ``bench/drivers/<kind>.py`` drives it, and each
per-layer metric is read by ``bench/metrics/<metric>.py``. Adding a cell,
configuration, traffic mix, driver or metric adds files and entries; no
existing file changes.

A driver module defines ``Driver(ctx)``, whose constructor is the set-up
(inputs from the seed, warm-up of every shape), and three methods:

* ``window(seconds)``: the measured loop. Returns a record with
  ``metrics`` (end-to-end values by name), ``attempted`` and whatever the
  metric readers and ``verify`` need.
* ``release()``: drop the program's state once the window has closed.
* ``verify(record)``: compare what the window produced with the plain
  reference; returns ``(checks, failed)`` where ``checks`` maps a short
  name to ``{"value", "limit"}`` and a check holds when value <= limit.

A metric module defines ``read(view)`` returning a number, or ``None``
when it finds nothing to read in this run.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SetupError(RuntimeError):
    """The run cannot start: no chip, a missing file, an unknown name."""


@dataclass
class Context:
    """What a driver's set-up gets."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    data_dir: Path
    info: dict = field(default_factory=dict)

    def note(self, key: str, value: Any) -> None:
        """Record a number for an earlier line of the output."""
        self.info[key] = value


@dataclass
class MetricView:
    """What a per-layer metric reader gets."""

    config: dict
    traffic: dict
    record: dict
    trace: Optional[dict]
    peak: dict


class CompileClock:
    """Backend compilations seen through JAX's monitoring events, from
    every thread."""

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += float(duration)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SetupError(f"{path.relative_to(ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(benchmark: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) for a workload name."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def cell_metrics(benchmark: dict, cell: str) -> tuple[list, list]:
    """(end-to-end metrics, per-layer metrics) this cell reports."""
    e2e = [m for m in benchmark["end_to_end"] if _reports(m, cell, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if _reports(m, cell, names)]
    return e2e, per_layer


def check_device(chips: int, peaks: dict):
    """The first device must be a TPU in the peak table, with enough of
    them; anything else is a set-up error, never a CPU fallback."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SetupError(f"no TPU: JAX's first device is {dev.platform!r}")
    if dev.device_kind not in peaks:
        raise SetupError(f"device kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides: Optional[dict] = None,
             driver_hook=None) -> dict:
    """One run; returns the result line as a dict (see module doc).

    ``overrides`` replaces configuration or traffic keys and may name the
    data directory (the tests run cells at small sizes on the CPU), and
    ``driver_hook(driver)`` may swap the system under test after set-up
    (the control runs).
    """
    import jax

    import traces

    benchmark = load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic = resolve_cell(benchmark, workload)
    for key, value in (overrides or {}).get("config", {}).items():
        config[key] = value
    for key, value in (overrides or {}).get("traffic", {}).items():
        traffic[key] = value
    peaks = load_json(BENCH / "peaks.json")
    if require_tpu:
        devices = check_device(int(cell["chips"]), peaks)
        peak = peaks[devices[0].device_kind]
    else:
        devices = jax.devices()
        peak = next(iter(peaks.values()))
    ctx_start_s = time.perf_counter() - t_start
    e2e, per_layer = cell_metrics(benchmark, workload)
    driver_mod = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                             f"bench_driver_{traffic['driver']}")
    clock = CompileClock()
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=int(seed),
                  data_dir=Path((overrides or {}).get("data_dir",
                                                      BENCH / ".data")))
    ctx.note("runtime_start_s", ctx_start_s)
    driver = driver_mod.Driver(ctx)
    if driver_hook is not None:
        driver_hook(driver)
    setup_s = time.perf_counter() - t_start
    compiles_before, compile_s_before = clock.count, clock.seconds

    trace_rec = None
    if trace:
        span = min(float(seconds), float(traffic.get("trace_seconds",
                                                     seconds)))
        trace_dir = str(BENCH / ".traces" / workload)
        with traces.recording(trace_dir):
            with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
                record = driver.window(span)
        trace_rec = traces.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
            record = driver.window(float(seconds))
    ctx.note("compiles_in_window", clock.count - compiles_before)
    ctx.note("compile_s_in_window", clock.seconds - compile_s_before)
    ctx.note("compiles_in_setup", compiles_before)
    ctx.note("compile_s_in_setup", compile_s_before)
    mem = memory_peak(devices)
    driver.release()
    t0 = time.perf_counter()
    checks, failed = driver.verify(record)
    ctx.note("verify_s", time.perf_counter() - t0)

    metrics = {}
    if not trace:
        values = dict(record["metrics"], setup_s=setup_s)
        for m in e2e:
            if m["name"] not in values:
                raise RuntimeError(f"driver {traffic['driver']!r} did not "
                                   f"measure {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        view = MetricView(config=config, traffic=traffic, record=record,
                          trace=trace_rec, peak=peak)
        for m in per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct,
              "attempted": int(record["attempted"]), "failed": int(failed),
              "metrics": metrics, "device": device}
    if trace and trace_rec is not None:
        lo, hi = traces.window_of(trace_rec)
        device["busy_s"] = traces.busy_ns(trace_rec) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": traces.top_ops(trace_rec),
                               "idle_gaps": traces.idle_gaps(trace_rec)}
    result["checks"] = checks
    result["_info"] = ctx.info
    return result


def _finite(value):
    """A non-finite reading as a string: JSON has no infinity."""
    if isinstance(value, float) and not math.isfinite(value):
        return str(value)
    return value


def print_result(result: dict) -> None:
    """Earlier lines (``# name: value``), the checks as the last lines of
    standard error, and the result as the last line of standard output."""
    info = result.pop("_info", {})
    for key, value in info.items():
        print(f"# {key}: {json.dumps(value)}", flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    checks = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
              for k, v in result["checks"].items()}
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def prepare_environment() -> None:
    """Paths the program reads from the environment, all inside the
    checkout; set before JAX or the program is imported."""
    os.environ["REPRO_TRACE_CACHE"] = str(BENCH / ".store")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
