"""Measured-vs-modeled conformance: pin the closed forms to compiled bytes.

The paper (Sec. III) concedes that "validation of the data movement models
is difficult" because the accelerators' simulators are closed-source.  The
TPU adaptation has no such excuse: the XLA-compiled Pallas programs are
open ground truth.  This subsystem compares, per movement level where
attributable, the analytical predictions of every registered dataflow that
declares a runnable kernel analogue (``DataflowSpec.runnable``) against
byte measurements of the compiled programs, across a grid of operating
points.  Methodology recorded in DESIGN.md §10.

Measurement layers (each a ``ConformanceRecord.source``):

``block_schedule``
    The Pallas pipeline's DMA schedule, traced from the kernel's *own*
    grid + BlockSpec index maps (re-exported by the kernel modules'
    ``*_block_streams`` helpers): iterate the grid in launch order (last
    dimension fastest), evaluate each operand's index map, and count a
    block transfer whenever the block index changes — Pallas elides the
    copy when consecutive steps revisit the same block.  This is the HBM
    traffic the compiled kernel performs on hardware, and it attributes
    bytes to individual movement levels.
``entry_boundary``
    Exact operand/result bytes of each compiled executable, parsed from
    the optimized HLO ENTRY signature (:func:`~repro.core.hlo_analysis.
    entry_boundary_bytes`).  For the unfused aggregate/combine pair the
    inter-phase buffer crosses this boundary twice, so the fused-minus-
    unfused boundary delta measures exactly the paper's eliminated
    ``K*N*sigma + P_s*N*sigma`` terms.
``cost_analysis``
    ``compiled.cost_analysis()['bytes accessed']``, asserted as a one-sided
    floor (measured >= boundary), not an equality, on every backend.  On
    the CPU the kernels run in Pallas interpret mode, whose lowering adds
    loop-machinery traffic; compiled for a TPU (``interpret=None`` there,
    see :func:`repro.kernels.resolve_interpret`) the kernel is one
    ``tpu_custom_call`` and the record sits just above the boundary.
``hlo_collectives``
    Wire bytes from :func:`~repro.core.hlo_analysis.parse_collectives` —
    zero for these single-device programs, and the hook through which the
    sharded kernels of later PRs join the same harness.

Every record carries a *declared tolerance*: schedule and boundary sources
are exact algebra over identical block geometry, so their tolerance is a
float64 epsilon; one-sided sources declare the slack direction instead.

This dynamic harness has a static counterpart: ``repro.analysis``
(DESIGN.md §16) audits the same closed forms symbolically — unit
consistency, symbol provenance, float64-exactness bounds — without
compiling anything.  :func:`run_conformance` runs that audit as a
preflight so byte measurements are never taken against a model that is
already known to be mis-transcribed.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Callable, Iterable, Mapping, Optional, Sequence

import numpy as np

from .dataflow import DataflowSpec
from .hlo_analysis import entry_boundary_bytes, parse_collectives
from .notation import GraphTileParams

__all__ = [
    "OperatingPoint",
    "ConformanceRecord",
    "ProgramMeasurement",
    "FusedSpMMAnalogue",
    "UnfusedSpMMAnalogue",
    "default_operating_points",
    "schedule_stream_bytes",
    "measure_program",
    "measure_analogue",
    "conformance_records",
    "interphase_delta_records",
    "run_conformance",
    "verify_numerics",
    "summarize_records",
    "EXACT_REL_TOL",
]

#: Declared tolerance for sources that are exact algebra in float64.
EXACT_REL_TOL = 1e-9


@dataclass(frozen=True)
class OperatingPoint:
    """One compile point of the kernel sweep: tile sizes in the paper's
    notation (K vertices, N in-features, T out-features) plus the kernel
    block shape — the node-block/feature/tile-size axes of the sweep."""

    K: int
    N: int
    T: int
    Bn: int
    Bk: int
    elem_bytes: float = 4.0   # f32 kernels; sigma = 8 * elem_bytes bits

    def __post_init__(self) -> None:
        if self.K % self.Bn or self.K % self.Bk:
            raise ValueError(f"K={self.K} must divide into Bn={self.Bn} / "
                             f"Bk={self.Bk} blocks (the kernels assert this)")

    @property
    def sigma_bits(self) -> float:
        return 8.0 * self.elem_bytes

    def graph(self) -> GraphTileParams:
        """The tile in Table II notation.  L and P do not enter the
        block-dense closed forms; they carry the paper's defaults."""
        return GraphTileParams(N=self.N, T=self.T, K=self.K,
                               L=self.K // 10, P=10 * self.K)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def default_operating_points() -> tuple[OperatingPoint, ...]:
    """The default conformance sweep: 10 points over node-block size K,
    feature width N, and kernel tile shape (Bn, Bk), including the
    single-source-block (nbk == 1) and single-dst-block (nbn == 1)
    schedules whose DMA elision degenerates."""
    pts = [OperatingPoint(K, N, 8, Bn, Bk)
           for K in (256, 512)
           for N in (16, 32)
           for Bn, Bk in ((128, 128), (128, 256))]
    pts.append(OperatingPoint(256, 16, 8, 256, 256))   # single block: all resident
    pts.append(OperatingPoint(512, 32, 8, 512, 128))   # nbn == 1: one dst row
    return tuple(pts)


@dataclass(frozen=True)
class ConformanceRecord:
    """One analytical-vs-measured comparison with a declared tolerance."""

    dataflow: str
    movement: str          # movement-level name or an aggregate probe
    source: str            # block_schedule | entry_boundary | cost_analysis | hlo_collectives
    point: Mapping
    analytical_bytes: float
    measured_bytes: float
    tolerance: float
    one_sided: bool = False   # pass iff measured >= analytical * (1 - tol)

    @property
    def ratio(self) -> float:
        """measured / analytical (1.0 when both sides are zero)."""
        if self.analytical_bytes == 0.0:
            return 1.0 if self.measured_bytes == 0.0 else float("inf")
        return self.measured_bytes / self.analytical_bytes

    @property
    def ok(self) -> bool:
        if self.one_sided:
            return self.measured_bytes >= self.analytical_bytes * (1.0 - self.tolerance)
        if self.analytical_bytes == 0.0:
            return self.measured_bytes == 0.0
        return abs(self.ratio - 1.0) <= self.tolerance

    def as_row(self) -> dict:
        row = {"dataflow": self.dataflow, "movement": self.movement,
               "source": self.source,
               "analytical_bytes": self.analytical_bytes,
               "measured_bytes": self.measured_bytes,
               "ratio": self.ratio, "tolerance": self.tolerance,
               "one_sided": self.one_sided, "ok": self.ok}
        row.update({k: v for k, v in dict(self.point).items()})
        return row

    def __str__(self) -> str:  # pragma: no cover - repr
        flag = "OK " if self.ok else "FAIL"
        return (f"[{flag}] {self.dataflow}.{self.movement} ({self.source}): "
                f"analytical={self.analytical_bytes:.6g}B "
                f"measured={self.measured_bytes:.6g}B ratio={self.ratio:.4f}")


@dataclass(frozen=True)
class ProgramMeasurement:
    """One compiled program plus its movement-attributed stream geometry."""

    label: str
    compiled: object                 # jax.stages.Compiled
    grid: tuple[int, ...]
    streams: Mapping[str, Mapping]   # movement name -> stream descriptor


def schedule_stream_bytes(grid: Sequence[int], stream: Mapping) -> dict:
    """Trace one operand's DMA schedule over the launch grid.

    Grid steps iterate in launch order (last dimension fastest).  A block
    transfer is counted whenever the evaluated index map differs from the
    previous step's — the Pallas pipeline skips the copy on revisits.
    Returns ``{"bytes", "transfers", "distinct_bytes", "distinct_blocks"}``;
    ``distinct_bytes`` is the union footprint (each block once), i.e. the
    executable-boundary share of this operand.
    """
    index_map: Callable = stream["index_map"]
    block_elems = math.prod(int(d) for d in stream["block_shape"])
    block_bytes = block_elems * float(stream["elem_bytes"])
    prev = None
    transfers = 0
    distinct: set[tuple] = set()
    for step in np.ndindex(*tuple(int(g) for g in grid)):
        idx = tuple(int(v) for v in index_map(*step))
        if idx != prev:
            transfers += 1
            prev = idx
        distinct.add(idx)
    return {
        "bytes": transfers * block_bytes,
        "transfers": transfers,
        "distinct_bytes": len(distinct) * block_bytes,
        "distinct_blocks": len(distinct),
    }


def measure_program(pm: ProgramMeasurement) -> dict:
    """All measurement layers for one compiled program."""
    hlo_text = pm.compiled.as_text()
    boundary = entry_boundary_bytes(hlo_text)
    collectives = parse_collectives(hlo_text)
    cost = pm.compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    per_stream = {name: schedule_stream_bytes(pm.grid, s)
                  for name, s in pm.streams.items()}
    return {
        "label": pm.label,
        "streams": per_stream,
        "stream_total_bytes": sum(s["bytes"] for s in per_stream.values()),
        "distinct_total_bytes": sum(s["distinct_bytes"]
                                    for s in per_stream.values()),
        "boundary": boundary,
        "collective_wire_bytes": collectives.total_wire_bytes_per_chip,
        "xla_bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }


class _SpMMAnalogueBase:
    """Shared machinery of the fused/unfused kernel analogues.

    Subclasses declare ``dataflow`` (the registered spec name) and
    ``programs(point, interpret=...)`` returning the compiled programs with
    their stream geometry.  Programs are lowered from abstract
    ``ShapeDtypeStruct`` operands — conformance measures compiled
    artifacts, so no input data ever materializes.
    """

    dataflow: str

    def graph_hw(self, spec: DataflowSpec, point: OperatingPoint):
        """The (graph, hw) pair putting the spec at the kernel's operating
        point: kernel dtype width as sigma, kernel blocks as Bn/Bk."""
        hw = spec.resolve_hw().replace(sigma=point.sigma_bits,
                                       sigma_adj=point.sigma_bits,
                                       Bn=point.Bn, Bk=point.Bk)
        return point.graph(), hw

    @staticmethod
    def _compile(fn, *shapes, **kwargs):
        import functools

        import jax
        jitted = jax.jit(functools.partial(fn, **kwargs))
        return jitted.lower(*shapes).compile()

    @staticmethod
    def _f32(*shape):
        import jax
        import jax.numpy as jnp
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def programs(self, point: OperatingPoint, *,
                 interpret: Optional[bool] = None
                 ) -> tuple[ProgramMeasurement, ...]:
        raise NotImplementedError


class FusedSpMMAnalogue(_SpMMAnalogueBase):
    """The fused aggregate+combine kernel <-> the ``spmm_tiled`` dataflow."""

    dataflow = "spmm_tiled"

    def programs(self, point: OperatingPoint, *,
                 interpret: Optional[bool] = None
                 ) -> tuple[ProgramMeasurement, ...]:
        from ..kernels import edge_aggregate as ea
        K, N, T = point.K, point.N, point.T
        compiled = self._compile(
            ea.fused_aggregate_combine,
            self._f32(K, K), self._f32(K, N), self._f32(N, T),
            block_n=point.Bn, block_k=point.Bk, interpret=interpret)
        acct = ea.fused_block_streams(K, N, T, block_n=point.Bn,
                                      block_k=point.Bk,
                                      elem_bytes=point.elem_bytes)
        return (ProgramMeasurement("fused", compiled, acct["grid"],
                                   acct["streams"]),)


class UnfusedSpMMAnalogue(_SpMMAnalogueBase):
    """The two-pass kernel pair <-> the ``spmm_unfused`` dataflow."""

    dataflow = "spmm_unfused"

    def programs(self, point: OperatingPoint, *,
                 interpret: Optional[bool] = None
                 ) -> tuple[ProgramMeasurement, ...]:
        from ..kernels import edge_aggregate_unfused as eu
        K, N, T = point.K, point.N, point.T
        agg = self._compile(
            eu.aggregate_pass, self._f32(K, K), self._f32(K, N),
            block_n=point.Bn, block_k=point.Bk, interpret=interpret)
        agg_acct = eu.aggregate_block_streams(K, N, block_n=point.Bn,
                                              block_k=point.Bk,
                                              elem_bytes=point.elem_bytes)
        comb = self._compile(
            eu.combine_pass, self._f32(K, N), self._f32(N, T),
            block_n=point.Bn, interpret=interpret)
        comb_acct = eu.combine_block_streams(K, N, T, block_n=point.Bn,
                                             elem_bytes=point.elem_bytes)
        return (
            ProgramMeasurement("aggregate", agg, agg_acct["grid"],
                               agg_acct["streams"]),
            ProgramMeasurement("combine", comb, comb_acct["grid"],
                               comb_acct["streams"]),
        )


def measure_analogue(analogue, point: OperatingPoint, *,
                     interpret: Optional[bool] = None) -> list[dict]:
    """Compile + measure every program of one analogue at one point.
    Compilation dominates the sweep cost — callers sharing a point should
    measure once and pass the result to the record builders."""
    return [measure_program(pm)
            for pm in analogue.programs(point, interpret=interpret)]


def conformance_records(spec: DataflowSpec, point: OperatingPoint, *,
                        interpret: Optional[bool] = None, analogue=None,
                        measures: list[dict] | None = None
                        ) -> list[ConformanceRecord]:
    """All conformance records of one dataflow at one operating point."""
    analogue = spec.runnable_analogue() if analogue is None else analogue
    graph, hw = analogue.graph_hw(spec, point)
    out = spec.evaluate(graph, hw)
    if measures is None:
        measures = measure_analogue(analogue, point, interpret=interpret)
    pt = point.as_dict()
    records: list[ConformanceRecord] = []

    # Per movement level where attributable: the traced DMA schedule.
    for meas in measures:
        for movement, traced in meas["streams"].items():
            records.append(ConformanceRecord(
                dataflow=spec.name, movement=movement,
                source="block_schedule", point=pt,
                analytical_bytes=float(out[movement].data_bits) / 8.0,
                measured_bytes=traced["bytes"],
                tolerance=EXACT_REL_TOL))

    # Off-chip total: every L2-class level must be covered by some stream.
    traced_total = sum(m["stream_total_bytes"] for m in measures)
    records.append(ConformanceRecord(
        dataflow=spec.name, movement="hbm_total", source="block_schedule",
        point=pt,
        analytical_bytes=float(out.offchip_bits()) / 8.0,
        measured_bytes=traced_total, tolerance=EXACT_REL_TOL))

    # Executable boundary: the compiled artifact's operand/result footprint
    # must equal the block cover of the declared streams.
    for meas in measures:
        records.append(ConformanceRecord(
            dataflow=spec.name, movement=f"boundary_{meas['label']}",
            source="entry_boundary", point=pt,
            analytical_bytes=meas["distinct_total_bytes"],
            measured_bytes=meas["boundary"]["total_bytes"],
            tolerance=EXACT_REL_TOL))

    # XLA's own accounting can only exceed the boundary floor.
    for meas in measures:
        records.append(ConformanceRecord(
            dataflow=spec.name, movement=f"xla_bytes_{meas['label']}",
            source="cost_analysis", point=pt,
            analytical_bytes=meas["boundary"]["total_bytes"],
            measured_bytes=meas["xla_bytes_accessed"],
            tolerance=0.0, one_sided=True))

    # Single-device programs move no collective bytes; the record keeps the
    # hlo_analysis hook live for the sharded kernels of later PRs.
    records.append(ConformanceRecord(
        dataflow=spec.name, movement="collective_wire",
        source="hlo_collectives", point=pt,
        analytical_bytes=0.0,
        measured_bytes=sum(m["collective_wire_bytes"] for m in measures),
        tolerance=0.0))
    return records


def interphase_delta_records(point: OperatingPoint, *,
                             interpret: Optional[bool] = None,
                             fused_measures: list[dict] | None = None,
                             unfused_measures: list[dict] | None = None
                             ) -> list[ConformanceRecord]:
    """Fused-minus-unfused measured bytes == the eliminated inter-phase terms.

    The paper's fusion claim (Sec. III / DESIGN.md §3): collapsing the
    inter-phase buffer into registers removes ``K*N*sigma`` write +
    ``P_s*N*sigma`` read traffic (``P_s = K`` in the block-dense analogue).
    Measured twice — at the executable boundary and in the traced DMA
    schedule — against ``spmm_unfused``'s analytical interphase levels.
    ``*_measures`` accept already-measured programs for this point
    (:func:`measure_analogue`) to avoid recompiling them.
    """
    from . import registry

    fused_spec = registry.get("spmm_tiled")
    unfused_spec = registry.get("spmm_unfused")
    fused = (fused_measures if fused_measures is not None else
             measure_analogue(fused_spec.runnable_analogue(), point,
                              interpret=interpret))
    unf_analogue = unfused_spec.runnable_analogue()
    unfused = (unfused_measures if unfused_measures is not None else
               measure_analogue(unf_analogue, point, interpret=interpret))
    graph, hw = unf_analogue.graph_hw(unfused_spec, point)
    out = unfused_spec.evaluate(graph, hw)
    eliminated = (float(out["writeinterphase"].data_bits)
                  + float(out["readinterphase"].data_bits)) / 8.0
    pt = point.as_dict()

    def _delta(key: Callable[[dict], float]) -> float:
        return sum(key(m) for m in unfused) - sum(key(m) for m in fused)

    return [
        ConformanceRecord(
            dataflow="spmm_unfused", movement="interphase_delta",
            source="entry_boundary", point=pt,
            analytical_bytes=eliminated,
            measured_bytes=_delta(lambda m: m["boundary"]["total_bytes"]),
            tolerance=EXACT_REL_TOL),
        ConformanceRecord(
            dataflow="spmm_unfused", movement="interphase_delta",
            source="block_schedule", point=pt,
            analytical_bytes=eliminated,
            measured_bytes=_delta(lambda m: m["stream_total_bytes"]),
            tolerance=EXACT_REL_TOL),
    ]


def run_conformance(names: Iterable[str] | None = None,
                    points: Sequence[OperatingPoint] | None = None, *,
                    interpret: Optional[bool] = None,
                    include_delta: bool = True,
                    preflight_audit: bool = True) -> list[ConformanceRecord]:
    """The full harness: every runnable dataflow x every operating point.

    With ``preflight_audit`` (the default) each dataflow is first passed
    through the static model auditor (``repro.analysis``, DESIGN.md §16)
    and the harness refuses to measure a model whose closed forms fail
    the unit/provenance/golden audit — dynamic conformance numbers for a
    statically broken model would only lend it false credibility.
    """
    from . import registry

    if names is None:
        names = [s.name for s in registry.specs() if s.has_runnable]
    else:
        names = list(names)
    if preflight_audit:
        from repro.analysis import audit_spec

        for name in names:
            errors = audit_spec(registry.get(name)).strict_errors()
            if errors:
                raise AssertionError(
                    f"static model audit failure for {name!r}; refusing to "
                    "measure (rerun with preflight_audit=False to override): "
                    + "; ".join(errors))
    points = default_operating_points() if points is None else points
    records: list[ConformanceRecord] = []
    measured: dict[tuple[str, OperatingPoint], list[dict]] = {}
    for name in names:
        spec = registry.get(name)
        analogue = spec.runnable_analogue()
        for pt in points:
            measures = measure_analogue(analogue, pt, interpret=interpret)
            measured[(name, pt)] = measures
            records.extend(conformance_records(spec, pt, interpret=interpret,
                                               analogue=analogue,
                                               measures=measures))
    if include_delta and {"spmm_tiled", "spmm_unfused"} <= set(names):
        for pt in points:
            records.extend(interphase_delta_records(
                pt, interpret=interpret,
                fused_measures=measured[("spmm_tiled", pt)],
                unfused_measures=measured[("spmm_unfused", pt)]))
    return records


def verify_numerics(point: OperatingPoint, *, seed: int = 0,
                    interpret: Optional[bool] = None) -> float:
    """Execute fused and unfused kernels at a point against the jnp oracle;
    returns the max relative error (conformance measures programs that
    compute the right thing, not just programs that move the right bytes)."""
    import jax.numpy as jnp

    from ..kernels import ops
    from ..kernels.ref import fused_aggregate_combine_ref

    rng = np.random.default_rng(seed)
    K, N, T = point.K, point.N, point.T
    a = jnp.asarray((rng.random((K, K)) < 0.05) * rng.random((K, K)),
                    jnp.float32)
    x = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((N, T)), jnp.float32)
    expect = fused_aggregate_combine_ref(a, x, w)
    fused = ops.gnn_aggregate_combine(a, x, w, block_n=point.Bn,
                                      block_k=point.Bk, interpret=interpret)
    unfused = ops.gnn_combine(
        ops.gnn_aggregate(a, x, block_n=point.Bn, block_k=point.Bk,
                          interpret=interpret),
        w, block_n=point.Bn, interpret=interpret)
    denom = float(jnp.max(jnp.abs(expect))) + 1e-9
    return max(float(jnp.max(jnp.abs(fused - expect))) / denom,
               float(jnp.max(jnp.abs(unfused - expect))) / denom)


def summarize_records(records: Sequence[ConformanceRecord]) -> dict:
    """Aggregate a record batch into the BENCH_conformance.json summary."""
    by_flow: dict[str, dict] = {}
    for r in records:
        e = by_flow.setdefault(r.dataflow, {"n_records": 0, "n_ok": 0,
                                            "max_abs_rel_err": 0.0})
        e["n_records"] += 1
        e["n_ok"] += int(r.ok)
        if not r.one_sided and np.isfinite(r.ratio):
            e["max_abs_rel_err"] = max(e["max_abs_rel_err"],
                                       abs(r.ratio - 1.0))
    return {
        "n_records": len(records),
        "n_ok": sum(int(r.ok) for r in records),
        "all_ok": all(r.ok for r in records),
        "by_dataflow": by_flow,
    }
