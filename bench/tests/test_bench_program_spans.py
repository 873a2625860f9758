"""The schedule engine's span and counter readers: a traced small-size run
of each cell on the CPU reports the new per-layer metrics in the sweep
cells only, and a program without the counters reports none of them."""

import time
from types import SimpleNamespace

import pytest

import harness
from test_bench_faults import SMALL, TRAFFIC

NAMES = ("schedule_upload_ms", "schedule_upload_mb", "schedule_dispatch_ms",
         "schedule_wait_ms", "schedule_host_ms")
EXPECTED = {"products.sweep": set(NAMES),
            "cora.sweep": {n + ".small" for n in NAMES},
            "cora.infer": set()}
ALL = set().union(*EXPECTED.values())


def traced_run(cell, tmp_path, records):
    def hook(cell_runner):
        window = cell_runner.window

        def recorded(seconds):
            records.append(window(seconds))
            return records[-1]
        cell_runner.window = recorded

    overrides = {"config": SMALL[cell], "traffic": TRAFFIC.get(cell, {}),
                 "data_dir": str(tmp_path / "data")}
    return harness.run_cell(cell, 987_654_321_012, 0.3, True,
                            t_start=time.perf_counter(), require_tpu=False,
                            overrides=overrides, driver_hook=hook)


@pytest.mark.parametrize("cell", sorted(EXPECTED))
def test_traced_run_reports_the_cells_span_metrics(cell, scratch_env):
    records = []
    result = traced_run(cell, scratch_env, records)
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    assert set(metrics) & ALL == EXPECTED[cell]
    if not EXPECTED[cell]:
        return
    suffix = "" if cell == "products.sweep" else ".small"
    value = {n: metrics[n + suffix]["value"] for n in NAMES}
    unique_pairs = result["_info"]["unique_pairs"]
    # Four int32/bool operands of 13 B per unique pair, and the stride.
    assert value["schedule_upload_mb"] == (13 * unique_pairs + 4) / 1e6
    times = [value[n] for n in NAMES if n.endswith("_ms")]
    assert all(t >= 0.0 for t in times) and value["schedule_wait_ms"] > 0
    record, = records
    assert sum(times) <= record["elapsed_s"] / record["capacities"] * 1e3


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "t_" + name.replace(".", "_"))


@pytest.mark.parametrize("record", [
    {"capacities": 4, "stats": {"schedule_computes": 4}},   # older program
    {"forwards": 3, "elapsed_s": 1.0},                      # infer cell
], ids=["no_span_counters", "no_stats"])
def test_readers_find_nothing_without_the_counters(record):
    view = SimpleNamespace(config={}, traffic={}, trace=None, peak={},
                           record=record)
    for name in sorted(ALL):
        assert _reader(name).read(view) is None, name


def test_readers_divide_by_capacities():
    stats = {"schedules_ns": 40_000_000, "schedule_upload_ns": 8_000_000,
             "schedule_upload_bytes": 548_928, "schedule_dispatch_ns": 400_000,
             "schedule_wait_ns": 20_000_000}
    view = SimpleNamespace(config={}, traffic={}, trace=None, peak={},
                           record={"capacities": 4, "stats": stats})
    got = {n: _reader(n).read(view) for n in NAMES}
    assert got == pytest.approx({
        "schedule_upload_ms": 2.0, "schedule_upload_mb": 0.137232,
        "schedule_dispatch_ms": 0.1, "schedule_wait_ms": 5.0,
        "schedule_host_ms": 2.9})
