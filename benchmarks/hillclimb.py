import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> measure.

Three cells (per the brief's selection rule):
  * gcn-cora x ogb_products        — most representative of the paper
                                     (GCN, collective-bound, worst useful ratio)
  * equiformer-v2 x ogb_products   — most collective-bound cell of the grid
  * qwen3-moe-30b-a3b x train_4k   — the MoE-a2a cell (paper's methodology
                                     generalized), memory-bound

Each experiment compiles a VARIANT of the baseline plan and records the
roofline terms to results/hillclimb/<name>.json.  The narrative lives in
EXPERIMENTS.md §Perf.

Run:  PYTHONPATH=src python -m benchmarks.hillclimb [--only NAME]
"""

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.hlo_analysis import parse_collectives
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell

RESULTS = Path(__file__).resolve().parents[1] / "results" / "hillclimb"


def measure(plan, mesh, scale: float) -> dict:
    lowered = plan.lower(mesh)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    stats = parse_collectives(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "flops_per_chip": float(cost.get("flops", 0.0)) * scale,
        "hbm_bytes_per_chip": float(cost.get("bytes accessed", 0.0)) * scale,
        "collective_bytes_per_chip": stats.total_wire_bytes_per_chip * scale,
        "by_kind": {k: v * scale for k, v in stats.by_kind().items()},
        "temp_bytes": mem.temp_size_in_bytes,
        "loop_scale": scale,
    }


def record(name: str, baseline: dict, variants: dict[str, dict],
           hypothesis: str) -> dict:
    RESULTS.mkdir(parents=True, exist_ok=True)
    rec = {"name": name, "hypothesis": hypothesis, "baseline": baseline,
           "variants": variants}
    (RESULTS / f"{name}.json").write_text(json.dumps(rec, indent=2))
    return rec


# ---------------------------------------------------------------------------
# HC-1: gcn-cora x ogb_products
# ---------------------------------------------------------------------------

def hc_gcn() -> dict:
    mesh = make_production_mesh()
    base_plan = build_cell("gcn-cora", "ogb_products", mesh)
    baseline = measure(base_plan, mesh, 1.0)

    # Variant A — bf16 feature pipeline: the wire traffic is raw node
    # features/activations; casting the aggregation path to bf16 should
    # halve both the all-gather and the scatter all-reduce bytes.
    # Variant A — aggregate in bf16: the transformed features crossing the
    # wire (gather of h, scatter all-reduce) halve in width; the dense
    # transforms stay f32.
    from repro.models.gnn import gcn as gcn_mod
    plan_a = build_cell("gcn-cora", "ogb_products", mesh)
    orig_loss = gcn_mod.loss_fn
    orig_fwd = gcn_mod.forward

    def fwd_bf16(cfg, params, g, **kw):
        kw["agg_dtype"] = jnp.bfloat16
        return orig_fwd(cfg, params, g, **kw)

    gcn_mod.forward = fwd_bf16
    try:
        plan_a = build_cell("gcn-cora", "ogb_products", mesh)
        var_a = measure(plan_a, mesh, 1.0)
    finally:
        gcn_mod.forward = orig_fwd

    # Variant B — nodes/edges sharded over dp only (16-way) instead of all
    # 256: the scatter-add's partial-sum all-reduce spans 16 ranks instead
    # of 256, trading parallel width for collective span.
    from repro.launch import steps as steps_mod
    orig_specs = steps_mod._gnn_graph_specs

    def dp_only_specs(arch, g, policy, shape):
        if arch.name == "gcn-cora":
            arch = __import__("dataclasses").replace(arch, name="meshgraphnet")
            out = orig_specs(arch, g, policy, shape)
            return out
        return orig_specs(arch, g, policy, shape)

    steps_mod._gnn_graph_specs = dp_only_specs
    try:
        plan_b = build_cell("gcn-cora", "ogb_products", mesh)
        var_b = measure(plan_b, mesh, 1.0)
    finally:
        steps_mod._gnn_graph_specs = orig_specs

    return record(
        "gcn_ogb_products", baseline,
        {"bf16_aggregation": var_a, "dp_only_sharding": var_b},
        hypothesis="collective term is feature bytes on the wire "
                   "(all-gather of transformed features + all-reduce of the "
                   "scatter); bf16 aggregation halves it / narrowing the "
                   "scatter's collective span shrinks the all-reduce")


# ---------------------------------------------------------------------------
# HC-2: equiformer-v2 x ogb_products
# ---------------------------------------------------------------------------

def hc_eqv2(gather_once: bool) -> dict:
    """Variant is toggled through the module flag GATHER_ONCE (see
    equiformer_v2._GATHER_ONCE) — gather/replicate node features once per
    layer instead of per edge chunk."""
    from repro.models.gnn import equiformer_v2 as eqv2
    mesh = make_production_mesh()
    eqv2._GATHER_ONCE = False
    base_plan = build_cell("equiformer-v2", "ogb_products", mesh)
    baseline = measure(base_plan, mesh, 12.0)
    eqv2._GATHER_ONCE = gather_once
    var_plan = build_cell("equiformer-v2", "ogb_products", mesh)
    variant = measure(var_plan, mesh, 12.0)
    eqv2._GATHER_ONCE = False
    return record(
        "eqv2_ogb_products", baseline, {"gather_once_per_layer": variant},
        hypothesis="the 64-chunk conv loop re-all-gathers the (N, L2, C/tp) "
                   "feature tensor every chunk (64x3.84 GB/layer on the "
                   "wire); hoisting one gather per layer cuts the all-gather "
                   "term ~64x at a +3.84 GB/device working-set cost")


# ---------------------------------------------------------------------------
# HC-3: qwen3-moe x train_4k
# ---------------------------------------------------------------------------

def _patched_arch(name: str, cfg_transform):
    """Temporarily swap REGISTRY[name] for a variant whose make_config is
    post-processed by ``cfg_transform`` (build_cell reads the registry)."""
    import contextlib
    import dataclasses
    from repro import configs as cfg_mod

    @contextlib.contextmanager
    def ctx():
        orig = cfg_mod.REGISTRY[name]
        patched = dataclasses.replace(
            orig, make_config=lambda **kw: cfg_transform(orig.make_config(**kw)))
        cfg_mod.REGISTRY[name] = patched
        try:
            yield
        finally:
            cfg_mod.REGISTRY[name] = orig

    return ctx()


def hc_qwen3() -> dict:
    import dataclasses
    mesh = make_production_mesh()

    base_plan = build_cell("qwen3-moe-30b-a3b", "train_4k", mesh)
    baseline = measure(base_plan, mesh, 48.0)

    # Variant A — remat "dots": save matmul outputs instead of full remat;
    # memory term should drop (no FFN recompute reads) at temp-bytes cost.
    with _patched_arch("qwen3-moe-30b-a3b",
                       lambda c: dataclasses.replace(c, remat="dots")):
        var_a = measure(build_cell("qwen3-moe-30b-a3b", "train_4k", mesh),
                        mesh, 48.0)

    # Variant B — tighter MoE capacity (1.25 -> 1.0): a2a payload and expert
    # GEMM bytes scale with capacity; 20% less dispatch traffic for a known,
    # bounded drop rate.
    with _patched_arch("qwen3-moe-30b-a3b",
                       lambda c: dataclasses.replace(
                           c, moe=dataclasses.replace(c.moe, capacity_factor=1.0))):
        var_b = measure(build_cell("qwen3-moe-30b-a3b", "train_4k", mesh),
                        mesh, 48.0)

    return record(
        "qwen3_train_4k", baseline,
        {"remat_dots": var_a, "capacity_1.0": var_b},
        hypothesis="memory term dominates: full remat re-reads every weight "
                   "in the backward recompute, and the MoE dispatch buffers "
                   "scale with the capacity factor")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    runs = {
        "gcn": hc_gcn,
        "eqv2": lambda: hc_eqv2(True),
        "qwen3": hc_qwen3,
    }
    for name, fn in runs.items():
        if args.only and args.only != name:
            continue
        rec = fn()
        b = rec["baseline"]
        print(f"== {rec['name']} ==")
        print(f"   baseline: flops={b['flops_per_chip']:.3e} "
              f"hbm={b['hbm_bytes_per_chip']:.3e} "
              f"coll={b['collective_bytes_per_chip']:.3e}")
        for vn, v in rec["variants"].items():
            print(f"   {vn:>22}: flops={v['flops_per_chip']:.3e} "
                  f"hbm={v['hbm_bytes_per_chip']:.3e} "
                  f"coll={v['collective_bytes_per_chip']:.3e} "
                  f"(x{v['collective_bytes_per_chip']/max(b['collective_bytes_per_chip'],1):.2f} coll, "
                  f"x{v['hbm_bytes_per_chip']/max(b['hbm_bytes_per_chip'],1):.2f} hbm)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
