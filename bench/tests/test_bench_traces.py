"""The reductions from a trace to numbers, on small traces."""

import json
from pathlib import Path

import pytest

import harness
import traces

DATA = Path(__file__).parent / "data"

RECORD = {
    "devices": {"/device:TPU:0": {
        "ops": [["%gnn_aggregate_combine.2 = f32[2816,16] custom-call(a)",
                 100, 50],
                ["%fusion.1 = f32[2816,16] fusion(b)", 120, 50],
                ["%copy.3 = f32[2816,7] copy(c)", 300, 20],
                ["%gnn_aggregate_combine.3 = f32[2816,7] custom-call(d)",
                 400, 100],
                ["%fusion.9 = f32[8] fusion(e)", 2000, 10]],
        "modules": [["jit_fwd(123)", 90, 200], ["jit_fwd(123)", 390, 150]]}},
    "spans": [["bench.window", 50, 1000],
              ["bench.infer.forward", 60, 300],
              ["bench.infer.forward", 380, 200]],
}


def test_stable_names():
    assert traces.stable_op_name(
        "%gnn_aggregate_combine.2 = f32[2816,16]{1,0} custom-call(x)"
    ) == "gnn_aggregate_combine"
    assert traces.stable_op_name("fusion.12") == "fusion"
    assert traces.stable_module_name(
        "jit__schedule_counts_jnp(8356401474049227050)"
    ) == "_schedule_counts_jnp"


def test_union():
    assert traces.union_ns([(0, 10), (5, 20), (30, 40), (35, 36)]) == 30
    assert traces.union_ns([]) == 0


def test_busy_is_the_union_inside_the_window():
    # [100,170] + [300,320] + [400,500]; the op at 2000 is outside
    assert traces.busy_ns(RECORD) == 190
    assert traces.window_of(RECORD) == (50, 1050)


def test_kernel_time_by_stable_name():
    assert traces.op_ns(RECORD, "gnn_aggregate_combine") == 150
    assert traces.op_ns(RECORD, "fusion") == 50


def test_idle_gaps_named_after_the_innermost_span():
    assert traces.idle_gaps(RECORD) == [
        ["outside bench spans", 550e-9],
        ["bench.infer.forward", 130e-9],
        ["bench.infer.forward", 80e-9],
        ["bench.infer.forward", 50e-9]]


def test_top_ops_by_program():
    assert traces.top_ops(RECORD) == [
        ["fwd:gnn_aggregate_combine", 150e-9], ["fwd:fusion", 50e-9],
        ["?:copy", 20e-9]]


def test_a_trace_without_a_window_is_an_error():
    with pytest.raises(ValueError):
        traces.window_of({"devices": {}, "spans": []})


def test_recorded_tpu_trace():
    """A few forwards of cora.infer recorded on a TPU v5e."""
    rec = json.loads((DATA / "tpu_cora_infer_trace.json").read_text())
    lo, hi = traces.window_of(rec)
    busy = traces.busy_ns(rec)
    kernel = traces.op_ns(rec, "gnn_aggregate_combine")
    assert 0 < kernel <= busy <= hi - lo
    gaps = traces.idle_gaps(rec)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert abs(sum(g[1] for g in traces.idle_gaps(rec, limit=10**6))
               - (hi - lo - busy) / 1e9) < 1e-9
    top = traces.top_ops(rec)
    assert top[0][0] == "program_forward:gnn_aggregate_combine"


def test_extract_reads_host_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a @ a)
    a = jnp.ones((64, 64))
    f(a).block_until_ready()
    with traces.recording(str(tmp_path / "tr")):
        with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.test.step"):
                with jax.profiler.TraceAnnotation("repro.test.inner"):
                    f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("other.span"):
                f(a).block_until_ready()
    rec = traces.load(str(tmp_path / "tr"))
    names = [s[0] for s in rec["spans"]]
    assert traces.WINDOW_SPAN in names and "bench.test.step" in names
    assert "repro.test.inner" in names and "other.span" not in names
    assert rec["devices"] == {}               # the CPU has no TPU plane
    assert traces.busy_ns(rec) == 0.0


def test_program_spans_name_the_gaps_and_change_no_reading():
    """One products.sweep sweep recorded on a TPU v5e, with the program's
    ``repro.*`` spans kept: every per-layer reading of the cell equals the
    one from the same trace with only ``bench.*`` spans, and the idle gaps
    keep their lengths but take the program's span names."""
    rec = json.loads((DATA / "tpu_products_sweep_trace.json").read_text())
    bench_only = dict(rec, spans=[s for s in rec["spans"]
                                  if s[0].startswith("bench.")])
    assert len(bench_only["spans"]) < len(rec["spans"])
    benchmark = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell, config, traffic = harness.resolve_cell(benchmark, "products.sweep")
    _, per_layer = harness.cell_metrics(benchmark, "products.sweep")
    peak = next(iter(harness.load_json(harness.BENCH / "peaks.json")
                     .values()))
    record = {"sweeps": 1, "capacities": 4, "unique_pairs": 123_718_280,
              "stats": {"schedules_ns": 4_100_000_000,
                        "schedule_upload_ns": 2_400_000_000,
                        "schedule_upload_bytes": 6_433_350_576,
                        "schedule_dispatch_ns": 1_700_000,
                        "schedule_wait_ns": 620_000_000}}
    readings = []
    for trace in (rec, bench_only):
        view = harness.MetricView(config=config, traffic=traffic,
                                  record=record, trace=trace, peak=peak)
        readings.append({m["name"]: harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py",
            "t_" + m["name"].replace(".", "_")).read(view)
            for m in per_layer})
    assert readings[0] == readings[1]
    assert readings[0]["schedule_device_ms"] > 0
    assert traces.top_ops(rec) == traces.top_ops(bench_only)
    gaps, old_gaps = traces.idle_gaps(rec), traces.idle_gaps(bench_only)
    assert [g[1] for g in gaps] == [g[1] for g in old_gaps]
    assert {g[0] for g in old_gaps} == {"bench.sweep.schedules"}
    assert gaps[0][0] == "repro.trace.schedules"
    assert gaps[1][0] == "repro.trace.schedule.upload"
