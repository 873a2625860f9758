"""§Roofline: three-term roofline per (arch x shape) from the dry-run
artifacts (single-pod mesh per the brief; the multi-pod pass proves the pod
axis shards).

Reads results/dryrun/single/*.json, emits one row per cell with:
  compute_s / memory_s / collective_s, the dominant term, MODEL_FLOPS,
  the useful-FLOP ratio, and the achieved roofline fraction.
"""

from __future__ import annotations

import json
from pathlib import Path

from repro.core.tpu_model import RooflineReport, roofline

RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"


def load_reports(mesh: str = "single") -> list[RooflineReport]:
    """XLA's cost analysis counts a while/scan body ONCE (verified by a
    layer-count probe, EXPERIMENTS.md §Roofline); every record carries the
    layer-loop trip count as ``loop_scale`` and all three terms scale by it.
    Residual undercount from inner chunk loops (q-chunks, CE chunks) is
    documented per cell."""
    reports = []
    for p in sorted((RESULTS / mesh).glob("*.json")):
        rec = json.loads(p.read_text())
        if not rec.get("ok"):
            continue
        scale = float(rec.get("meta", {}).get("loop_scale", 1) or 1)
        reports.append(roofline(
            cell=f"{rec['arch']}::{rec['shape']}",
            chips=rec["chips"],
            flops_per_chip=rec["cost"]["flops"] * scale,
            hbm_bytes_per_chip=rec["cost"]["bytes_accessed"] * scale,
            collective_bytes_per_chip=(
                rec["collectives"]["wire_bytes_per_chip"] * scale),
            model_flops=rec["model_flops"],
            meta={"kind": rec.get("kind"), "mesh": mesh, "loop_scale": scale},
        ))
    return reports


def rows(mesh: str = "single") -> list[dict]:
    out = []
    for rep in load_reports(mesh):
        r = rep.row()
        r["mesh"] = mesh
        out.append(r)
    return out


def render_table(mesh: str = "single") -> str:
    lines = [f"{'cell':<42}{'compute_s':>11}{'memory_s':>11}{'coll_s':>11}"
             f"{'dominant':>11}{'useful':>8}{'roofl%':>8}"]
    for r in rows(mesh):
        lines.append(
            f"{r['cell']:<42}{r['compute_s']:>11.3e}{r['memory_s']:>11.3e}"
            f"{r['collective_s']:>11.3e}{r['dominant']:>11}"
            f"{r['useful_flop_ratio']:>8.2f}"
            f"{100 * r['roofline_fraction']:>7.1f}%")
    return "\n".join(lines)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(render_table())
