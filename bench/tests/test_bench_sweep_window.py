"""The sweep driver's measured window: whole sweeps only, and one
``sweep_ms`` entry per sweep that add up to the window."""

import time
from types import SimpleNamespace

import numpy as np
import pytest

import harness
from test_bench_faults import SMALL, TRAFFIC

sweep = harness.load_module(harness.BENCH / "drivers" / "sweep.py",
                            "t_bench_driver_sweep")


class FakeTrace:
    """Stands in for a ``GraphTrace``: each schedule takes ``step_s``."""

    n_edges = 10

    def __init__(self, step_s: float) -> None:
        self.step_s = step_s
        self.calls = 0

    def clear_schedules(self) -> None:
        pass

    def schedules(self, caps, engine):
        time.sleep(self.step_s)
        self.calls += 1
        return [self.calls] * len(caps)


def fake_driver(step_s: float, caps=(4, 8)):
    stats = {"schedule_computes": 0}
    drv = sweep.Driver.__new__(sweep.Driver)
    drv._rtrace = SimpleNamespace(
        trace_cache_info=lambda: {"stats": dict(stats)})
    drv.ctx = harness.Context(cell={}, config={}, traffic={}, seed=1,
                              data_dir=None)
    drv.trace = FakeTrace(step_s)
    drv.engine, drv.metric, drv.caps = "jax", "schedule_ms", list(caps)
    drv.rng = np.random.default_rng(0)
    return drv


@pytest.mark.parametrize("step_s,seconds", [(0.02, 0.1), (0.05, 0.01)])
def test_window_stops_on_the_first_sweep_boundary_past_seconds(step_s,
                                                                seconds):
    drv = fake_driver(step_s)
    record = drv.window(seconds)
    sweep_s = [ms / 1e3 for ms in drv.ctx.info["sweep_ms"]]
    assert record["sweeps"] == len(sweep_s) == drv.trace.calls
    assert sum(sweep_s[:-1]) < seconds <= sum(sweep_s)
    assert record["capacities"] == record["attempted"] == \
        record["sweeps"] * len(drv.caps)
    assert record["metrics"]["schedule_ms"] == pytest.approx(
        record["elapsed_s"] / record["capacities"] * 1e3)


def test_sweep_ms_has_one_entry_per_sweep_and_adds_up_to_the_window():
    drv = fake_driver(0.01)
    record = drv.window(0.15)
    noted = drv.ctx.info["sweep_ms"]
    assert len(noted) == record["sweeps"] >= 10
    assert sum(noted) / 1e3 == pytest.approx(record["elapsed_s"], rel=1e-2)
    assert all(s >= 10.0 for s in noted)      # each waits for its sweep


def test_small_products_run_notes_every_sweep(scratch_env):
    records = []

    def hook(drv):
        window = drv.window

        def recorded(seconds):
            records.append(window(seconds))
            return records[-1]
        drv.window = recorded

    overrides = {"config": SMALL["products.sweep"],
                 "traffic": TRAFFIC["products.sweep"],
                 "data_dir": str(scratch_env / "data")}
    result = harness.run_cell("products.sweep", 4_123_456_789, 0.3, False,
                              t_start=time.perf_counter(),
                              require_tpu=False, overrides=overrides,
                              driver_hook=hook)
    assert result["correct"], result["checks"]
    record, = records
    noted = result["_info"]["sweep_ms"]
    assert len(noted) == record["sweeps"] == len(record["kept"]) - 1
    assert result["attempted"] == record["sweeps"] * 4
    assert sum(noted) / 1e3 == pytest.approx(record["elapsed_s"], rel=1e-2)
    assert sum(noted[:-1]) / 1e3 < 0.3 <= sum(noted) / 1e3 + 1e-6
