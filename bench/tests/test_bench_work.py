"""Work counts against hand counts at GCN-Cora sizes."""

from types import SimpleNamespace

import pytest

import work

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
N, NNZ = 2708, 10556 + 2708          # edges plus self-loops


def test_gcn_cora_layer1():
    w = work.gcn_layer(N, NNZ, 1433, 16)
    # (Â X) W = 38,014,624 + 124,178,048; Â (X W) = 124,178,048 + 424,448
    assert w.flops == 124_602_496
    # 4 * (2708*1433 + 1433*16 + 2708*16) + 12 * 13264
    assert w.bytes == 15_946_448


def test_gcn_cora_layer2():
    w = work.gcn_layer(N, NNZ, 16, 7)
    assert w.flops == 792_288                     # Â (H W) is cheaper
    assert w.bytes == 408_752


def test_gcn_cora_forward_and_least_time():
    w = work.gcn_forward(N, NNZ, (1433, 16, 7))
    assert w.flops == 125_394_784
    assert w.bytes == 16_355_200
    # memory bound: 16,355,200 B / 819 GB/s
    assert work.least_seconds(w, PEAK) == pytest.approx(1.99697e-5, rel=1e-5)


def test_schedule_sweep_bytes_at_products():
    assert work.schedule_sweep_bytes(123_718_280) == 1_484_619_360


def _reader(name):
    import harness

    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "test_metric_" + name.replace(".", "_"))


CORA = {"n_nodes": N, "d_in": 1433, "d_hidden": 16, "n_classes": 7}


def _kernel_trace(kernel_ns_per_forward, forwards):
    ops = [[f"%gnn_aggregate_combine.{i} = f32[2816,16] custom-call()",
            1000 + i * 2 * kernel_ns_per_forward, kernel_ns_per_forward]
           for i in range(forwards)]
    end = 1000 + forwards * 2 * kernel_ns_per_forward
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "spans": [["bench.window", 0, end]]}


def test_fused_roofline_reader():
    view = SimpleNamespace(config=CORA, traffic={}, peak=PEAK,
                           record={"forwards": 2, "nnz": NNZ},
                           trace=_kernel_trace(1_000_000, 2))
    # 1.99697e-5 s of least time per 1 ms of kernel time
    assert _reader("fused_roofline").read(view) == pytest.approx(1.99697,
                                                                 rel=1e-5)


def test_infer_mfu_reader():
    view = SimpleNamespace(config=CORA, traffic={}, peak=PEAK, trace=None,
                           record={"forwards": 1000, "nnz": NNZ,
                                   "elapsed_s": 1.6})
    # 125,394,784 FLOP per 1.6 ms over 197 TFLOP/s
    assert _reader("infer_mfu").read(view) == pytest.approx(0.0397826,
                                                            rel=1e-5)


def test_schedule_roofline_reader():
    rec = {"devices": {"/device:TPU:0": {"ops": [["%fusion = s32", 0,
                                                    2_000_000_000]],
                                         "modules": []}},
           "spans": [["bench.window", 0, 4_000_000_000]]}
    view = SimpleNamespace(config={}, traffic={}, peak=PEAK, trace=rec,
                           record={"sweeps": 1, "capacities": 4,
                                   "unique_pairs": 123_718_280})
    # 1.8127 ms of least time per 2 s of device time
    assert _reader("schedule_roofline").read(view) == pytest.approx(
        0.0906361, rel=1e-5)
    assert _reader("schedule_device_ms").read(view) == pytest.approx(500.0)
    assert _reader("device_idle.sweep").read(view) == pytest.approx(50.0)


def test_readers_find_nothing_without_a_trace():
    view = SimpleNamespace(config=CORA, traffic={}, peak=PEAK, trace=None,
                           record={"forwards": 3, "nnz": NNZ, "sweeps": 1,
                                   "capacities": 4, "unique_pairs": 10})
    for name in ("fused_roofline", "schedule_roofline", "schedule_device_ms",
                 "device_idle.infer", "device_idle.sweep"):
        assert _reader(name).read(view) is None, name
