"""Exhaustive-oracle battery for the §15 design-space auto-tuner (ISSUE 7).

Four families of guarantees:

* **Oracle parity** — on every small search space the tuner must be
  *bit-identical* to an independent brute force: enumerate the full
  cross-product in the same canonical order, evaluate each candidate
  with the one-scenario planner path, mask by the SRAM working-set
  model, ``np.argmin``.  Covered for all five registered dataflows,
  uniform full-graph and trace graph kinds, and both residencies —
  hypothesis-driven where installed, seeded deterministic shim
  otherwise (the :mod:`test_properties` pattern).
* **Search invariants** — the winning objective is monotone
  non-increasing as the SRAM budget relaxes; the Pareto frontier is
  pairwise non-dominated and strictly shaped; a one-point space returns
  exactly that point; a budget below every working set raises the typed
  :class:`repro.core.InfeasibleBudgetError`.
* **Cache reuse** — a multi-capacity tune over a trace dataset performs
  exactly ONE sorted-edge factorization and ONE trace build
  (regression-gated via ``trace_cache_info()["stats"]``).
* **CLI contract** — ``--tune`` schema errors (unknown axis, negative
  budget, non-finite objective weight, plain scenario in a tune batch,
  mode mixing) exit 2 with a one-line ``error:`` message; golden-pin
  drift exits 1; plus the previously-unasserted ``--scenario`` error
  exit codes (missing file, invalid JSON, unknown scenario key, unknown
  dataflow, bad expect key).
"""

import json
import math

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic fallback: same shapes, seeded draws
    HAVE_HYPOTHESIS = False

    class _IntStrategy:
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi

        def draw(self, rng):
            return int(rng.integers(self.lo, self.hi + 1))

    class _SampledStrategy:
        def __init__(self, elems):
            self.elems = list(elems)

        def draw(self, rng):
            return self.elems[int(rng.integers(len(self.elems)))]

    class st:  # noqa: N801 - mirrors the hypothesis namespace
        integers = staticmethod(lambda lo, hi: _IntStrategy(lo, hi))
        sampled_from = staticmethod(_SampledStrategy)

    def settings(**_kw):
        return lambda fn: fn

    def given(*strategies, n_examples=8):
        def deco(fn):
            import functools
            import inspect

            sig_params = list(inspect.signature(fn).parameters.values())
            drawn = [p.name
                     for p in sig_params[len(sig_params) - len(strategies):]]

            @functools.wraps(fn)
            def wrapper(**kwargs):
                rng = np.random.default_rng(0)
                for _ in range(n_examples):
                    fn(**kwargs, **{nm: s.draw(rng)
                                    for nm, s in zip(drawn, strategies)})

            wrapper.__signature__ = inspect.Signature(
                [p for p in sig_params if p.name not in drawn])
            return wrapper
        return deco

from repro.api import (Composition, Scenario, evaluate_scenario,
                       evaluate_scenarios)
from repro.api.cli import main as cli_main
from repro.core import (InfeasibleBudgetError, clear_trace_cache, registry,
                        reset_trace_stats, tile_working_set_bits,
                        trace_cache_info, tune_scenario)
from repro.core.tune import normalize_optimize

ALL_DATAFLOWS = registry.names()

# Tiny molecule-batch trace: token-less dataset (no on-disk schedule
# cache) and far below REPRO_TRACE_CACHE_MIN_EDGES, so every cache
# observation below is about the in-process machinery only.
MOL = {"batch": 8, "n_nodes": 30, "n_edges": 64, "seed": 0, "step": 0}


def uniform_scenario(optimize, V=512, widths=(64, 16, 8), tile_vertices=128,
                     **kw):
    return Scenario.full_graph(
        ALL_DATAFLOWS[0], V=float(V), E=float(8 * V), N=float(widths[0]),
        T=float(widths[-1]), widths=widths, tile_vertices=tile_vertices,
        label="tune-uniform", optimize=optimize, **kw)


def trace_scenario(optimize, params=MOL, widths=(16, 16, 16),
                   tile_vertices=32, **kw):
    return Scenario.trace(
        ALL_DATAFLOWS[0], dataset="molecule", params=params,
        N=float(widths[0]), T=float(widths[-1]), widths=widths,
        tile_vertices=tile_vertices, label="tune-trace",
        optimize=optimize, **kw)


def oracle(scenario):
    """Independent brute force in the tuner's canonical enumeration.

    One planner call per candidate (the un-batched path), feasibility
    from the same working-set closed form, winner by masked
    ``np.argmin`` — the reference the tuner must match bit for bit.
    """
    opt = scenario.optimize
    space = opt["space"]
    comp = scenario.composition
    if scenario.graph_kind == "trace":
        from repro.core import resolve_trace_dataset
        V = float(resolve_trace_dataset(scenario.graph["dataset"],
                                        scenario.graph["params"]).n_nodes)
    else:
        V = float(scenario.graph["V"])
    dataflows = space.get("dataflow")
    dataflows = (registry.names() if dataflows == "all"
                 else tuple(dataflows) if dataflows
                 else (scenario.dataflow,))
    residencies = tuple(space.get("residency") or (comp.residency,))
    halos = tuple(space.get("halo_dedup") or (comp.halo_dedup,))
    if "tile_vertices" in space:
        caps = tuple(space["tile_vertices"])
    elif "n_tiles" in space:
        caps = tuple(float(math.ceil(V / nt)) for nt in space["n_tiles"])
    else:
        caps = (float(comp.tile_vertices),)
    budget = opt["budget"]
    budget_bits = None if budget is None else budget["sram_bits"]

    cands, objs, srams = [], [], []
    for df in dataflows:
        sigma = float(scenario.hardware.get(
            "sigma", registry.get(df).hw_factory().sigma))
        for res in residencies:
            for hd in halos:
                for cap in caps:
                    c = scenario.replace(
                        dataflow=df, optimize=None, expect=None,
                        composition=Composition(
                            widths=comp.widths, residency=res,
                            tile_vertices=cap, halo_dedup=hd))
                    r = evaluate_scenario(c)
                    vals = {"movement": r.total_bits,
                            "offchip": r.offchip_bits,
                            "iterations": r.total_iterations}
                    obj = (float(vals[opt["objective"]])
                           if isinstance(opt["objective"], str) else
                           float(sum(w * vals[k]
                                     for k, w in opt["objective"].items())))
                    cands.append((df, cap, res, hd))
                    objs.append(obj)
                    srams.append(float(tile_working_set_bits(
                        cap, V=V, widths=comp.widths, sigma=sigma,
                        residency=res, halo_dedup=hd)))
    objs = np.asarray(objs)
    srams = np.asarray(srams)
    feas = (np.ones(len(cands), bool) if budget_bits is None
            else srams <= budget_bits)
    best = (None if not feas.any()
            else int(np.argmin(np.where(feas, objs, np.inf))))
    return cands, objs, srams, best


def assert_oracle_parity(scenario):
    cands, objs, srams, best = oracle(scenario)
    tr = tune_scenario(scenario)
    assert tr.method == "exhaustive"
    assert tr.n_candidates == tr.n_evaluated == len(cands)
    # every point, bit for bit, in the oracle's enumeration order
    for i, (p, c) in enumerate(zip(tr.points, cands)):
        assert p.index == i
        assert (p.dataflow, p.tile_vertices, p.residency,
                p.halo_dedup) == (c[0], float(c[1]), c[2], float(c[3]))
        assert p.objective == objs[i]
        assert p.sram_bits == srams[i]
    assert tr.best.index == best
    assert tr.best.objective == objs[best]
    return tr


# ---------------------------------------------------------------------------
# 1. Oracle parity
# ---------------------------------------------------------------------------

def test_uniform_oracle_parity_all_dataflows_both_residencies():
    tr = assert_oracle_parity(uniform_scenario({
        "objective": "movement",
        "space": {"dataflow": "all",
                  "tile_vertices": [64, 128, 256, 512],
                  "residency": ["spill", "resident"]}}))
    assert tr.n_candidates == len(ALL_DATAFLOWS) * 2 * 4
    # capacity batches along the planner axis: one broadcast group per
    # (dataflow, residency) cell, never one per capacity
    assert tr.n_groups == len(ALL_DATAFLOWS) * 2


def test_trace_oracle_parity_all_dataflows_both_residencies():
    assert_oracle_parity(trace_scenario({
        "objective": "movement",
        "space": {"dataflow": "all",
                  "tile_vertices": [16, 32, 64],
                  "residency": ["spill", "resident"]}}))


@pytest.mark.parametrize("objective",
                         ["offchip", "iterations",
                          {"movement": 1.0, "iterations": 5e3}])
def test_oracle_parity_alternate_objectives(objective):
    assert_oracle_parity(uniform_scenario({
        "objective": objective,
        "space": {"dataflow": "all", "tile_vertices": [64, 256]}}))


def test_oracle_parity_halo_and_n_tiles_axes():
    assert_oracle_parity(uniform_scenario({
        "objective": "movement",
        "space": {"n_tiles": [1, 2, 4, 8],
                  "halo_dedup": [1.0, 2.0, 4.0]}}))


def test_oracle_parity_budgeted():
    tr = assert_oracle_parity(uniform_scenario({
        "objective": "movement",
        "budget": {"sram_bits": 6e4},
        "space": {"dataflow": "all",
                  "tile_vertices": [64, 128, 256, 512],
                  "residency": ["spill", "resident"]}}))
    assert tr.best.sram_bits <= 6e4
    assert tr.n_feasible < tr.n_candidates  # the budget actually bites


@settings(max_examples=10, deadline=None) if HAVE_HYPOTHESIS else (lambda f: f)
@given(st.integers(64, 2048), st.integers(2, 64), st.integers(1, 4),
       st.sampled_from(["movement", "offchip", "iterations"]))
def test_oracle_parity_hypothesis(V, w_hidden, n_caps, objective):
    caps = [2 ** (4 + i) for i in range(n_caps)]
    assert_oracle_parity(uniform_scenario(
        {"objective": objective,
         "space": {"dataflow": "all", "tile_vertices": caps,
                   "residency": ["spill", "resident"]}},
        V=V, widths=(32, w_hidden, 8)))


def test_coordinate_descent_matches_exhaustive_here():
    """On these small well-behaved spaces the memoized coordinate descent
    lands on the same winner as the oracle (it is guaranteed to when at
    most one axis is multi-valued; these spaces are also unimodal enough
    per axis that the restart schedule finds the global best)."""
    opt = {"objective": "movement",
           "space": {"dataflow": "all",
                     "tile_vertices": [64, 128, 256, 512],
                     "residency": ["spill", "resident"]}}
    ex = tune_scenario(uniform_scenario(opt))
    co = tune_scenario(uniform_scenario({**opt, "method": "coordinate"}))
    assert co.method == "coordinate"
    assert co.n_evaluated < co.n_candidates or co.n_candidates <= 8
    assert co.best.objective == ex.best.objective
    assert (co.best.dataflow, co.best.tile_vertices, co.best.residency) == \
        (ex.best.dataflow, ex.best.tile_vertices, ex.best.residency)


def test_auto_method_switches_on_max_exhaustive():
    opt = {"objective": "movement",
           "space": {"tile_vertices": [64, 128, 256, 512]}}
    assert tune_scenario(uniform_scenario(opt)).method == "exhaustive"
    small = tune_scenario(uniform_scenario({**opt, "max_exhaustive": 2}))
    assert small.method == "coordinate"
    # capacity is the only multi-valued axis: one full sweep of it is a
    # complete enumeration, so even the descent path is oracle-exact
    full = tune_scenario(uniform_scenario(opt))
    assert small.best.objective == full.best.objective
    assert small.best.index == full.best.index


# ---------------------------------------------------------------------------
# 2. Search invariants
# ---------------------------------------------------------------------------

def test_objective_monotone_as_budget_relaxes():
    space = {"dataflow": "all", "tile_vertices": [64, 128, 256, 512],
             "residency": ["spill", "resident"]}
    open_tr = tune_scenario(uniform_scenario(
        {"objective": "movement", "space": space}))
    srams = sorted({p.sram_bits for p in open_tr.points})
    prev = math.inf
    for budget in srams:
        tr = tune_scenario(uniform_scenario(
            {"objective": "movement", "space": space,
             "budget": {"sram_bits": budget}}))
        assert tr.best.sram_bits <= budget
        assert tr.best.objective <= prev
        prev = tr.best.objective
    # fully relaxed == unconstrained winner
    assert prev == open_tr.best.objective


def test_pareto_frontier_is_nondominated_and_strictly_shaped():
    tr = tune_scenario(uniform_scenario({
        "objective": "movement",
        "space": {"dataflow": "all",
                  "tile_vertices": [64, 128, 256, 512],
                  "residency": ["spill", "resident"]}}))
    fr = tr.frontier
    assert fr, "open-budget tune must produce a frontier"
    # strictly increasing sram, strictly decreasing objective
    for a, b in zip(fr, fr[1:]):
        assert a.sram_bits < b.sram_bits
        assert a.objective > b.objective
    # pairwise non-domination over the whole feasible point set
    feas = [p for p in tr.points if p.feasible]
    for p in fr:
        for q in feas:
            assert not (q.sram_bits <= p.sram_bits
                        and q.objective < p.objective)
    # the unconstrained winner is the frontier's last (largest-sram) point
    assert fr[-1].objective == tr.best.objective


def test_one_point_space_returns_that_point():
    base = uniform_scenario(None)
    tr = tune_scenario(base.replace(optimize={
        "objective": "movement",
        "space": {"tile_vertices": [base.composition.tile_vertices]}}))
    assert tr.n_candidates == tr.n_evaluated == 1
    assert tr.best.index == 0
    assert tr.best.tile_vertices == base.composition.tile_vertices
    assert tr.best.dataflow == base.dataflow
    # and it equals the plain evaluation of the base scenario
    plain = evaluate_scenario(base)
    assert tr.best.objective == plain.total_bits
    assert tr.best_result.total_bits == plain.total_bits
    assert tr.frontier == tr.points


def test_budget_below_every_footprint_raises_typed_error():
    with pytest.raises(InfeasibleBudgetError, match="below every explored"):
        tune_scenario(uniform_scenario({
            "objective": "movement",
            "budget": {"sram_bits": 1.0},
            "space": {"dataflow": "all", "tile_vertices": [64, 128]}}))
    # the typed error is a ValueError: the CLI's schema handling applies
    assert issubclass(InfeasibleBudgetError, ValueError)


def test_planner_routes_optimize_scenarios_and_orders_results():
    """A mixed batch: plain scenarios keep the broadcast path, optimize
    scenarios route through the tuner, results stay in input order."""
    plain = uniform_scenario(None)
    tuned = uniform_scenario({"objective": "movement",
                              "space": {"tile_vertices": [64, 128, 256]}})
    res = evaluate_scenarios([plain, tuned, plain])
    assert [r.scenario is s for r, s in
            zip(res.results, [plain, tuned, plain])] == [True] * 3
    assert res.results[0].total_bits == res.results[2].total_bits
    t = res.results[1].meta["tune"]
    assert t["best"]["objective"] == res.results[1].total_bits
    assert res.results[1].total_bits <= res.results[0].total_bits
    # evaluate_groups refuses optimize scenarios outright
    from repro.api import evaluate_groups
    with pytest.raises(ValueError, match="evaluate_scenarios"):
        evaluate_groups([tuned])


def test_tune_expect_pins_gate_best_configuration():
    opt = {"objective": "movement",
           "space": {"dataflow": "all", "tile_vertices": [64, 128, 256]}}
    tr = tune_scenario(uniform_scenario(opt))
    good = uniform_scenario(opt, expect={
        "objective": tr.best.objective,
        "best_dataflow": tr.best.dataflow,
        "best_tile_vertices": tr.best.tile_vertices})
    bad = uniform_scenario(opt, expect={"best_dataflow": "no-such-dataflow"})
    res = evaluate_scenarios([good, bad])
    assert res.results[0].expect_ok is True
    assert res.results[1].expect_ok is False


def test_optimize_block_round_trips_and_extends_plan_key():
    s = uniform_scenario({"objective": "movement",
                          "space": {"tile_vertices": [64, 128]}})
    s2 = Scenario.from_dict(json.loads(json.dumps(s.to_dict())))
    assert s2 == s
    assert s2.plan_key() == s.plan_key()
    assert s2.optimize == normalize_optimize(s2.optimize)  # idempotent
    plain = s.replace(optimize=None)
    assert plain.plan_key() != s.plan_key()


def test_optimize_schema_rejections():
    mk = uniform_scenario
    with pytest.raises(ValueError, match="unknown optimize space axis"):
        mk({"space": {"frobnicate": [1]}})
    with pytest.raises(ValueError, match="negative SRAM budget"):
        mk({"budget": {"sram_bits": -5}})
    with pytest.raises(ValueError, match="non-finite objective weight"):
        mk({"objective": {"movement": float("inf")}})
    with pytest.raises(ValueError, match="unknown objective"):
        mk({"objective": "latency"})
    with pytest.raises(ValueError, match="not both"):
        mk({"space": {"tile_vertices": [64], "n_tiles": [2]}})
    with pytest.raises(ValueError, match="exactly one"):
        mk({"budget": {"sram_bits": 1e6, "sram_bytes": 1e5}})
    with pytest.raises(ValueError, match="must not be empty"):
        mk({"space": {"tile_vertices": []}})
    with pytest.raises((ValueError, TypeError), match="optimize"):
        Scenario.tile(ALL_DATAFLOWS[0], optimize={"objective": "movement"})
    with pytest.raises(ValueError, match="mutually exclusive"):
        mk({"objective": "movement"}, conformance=True)
    with pytest.raises(ValueError, match="resident"):
        Scenario.trace(ALL_DATAFLOWS[0], dataset="molecule", params=MOL,
                       N=16.0, T=16.0, widths=None,
                       optimize={"space": {"residency": ["resident"]}})


# ---------------------------------------------------------------------------
# 3. Cache reuse: one factorization per dataset per tune run
# ---------------------------------------------------------------------------

def test_multi_capacity_trace_tune_is_one_factorization():
    params = {**MOL, "step": 7}  # fresh params: miss any earlier LRU entry
    clear_trace_cache()
    reset_trace_stats()
    tr = tune_scenario(trace_scenario({
        "objective": "movement",
        "space": {"dataflow": "all",
                  "tile_vertices": [8, 16, 32, 64, 128]}}, params=params))
    stats = trace_cache_info()["stats"]
    assert stats["trace_builds"] == 1
    assert stats["factorizations"] == 1
    # every (dataflow, capacity) cell evaluated, one schedule per capacity
    assert tr.n_evaluated == len(ALL_DATAFLOWS) * 5
    assert stats["schedule_computes"] == 5
    assert stats["schedule_cache_hits"] >= (len(ALL_DATAFLOWS) - 1) * 5


def test_reset_trace_stats_zeroes_all_counters():
    reset_trace_stats()
    stats = trace_cache_info()["stats"]
    assert set(stats) == {"factorizations", "schedule_computes",
                          "schedule_cache_hits", "schedule_disk_hits",
                          "trace_builds", "schedules_ns",
                          "schedule_upload_ns", "schedule_upload_bytes",
                          "schedule_factorization_uploads",
                          "schedule_factorization_reuses",
                          "schedule_dispatch_ns", "schedule_wait_ns",
                          "schedule_hist_mxu", "schedule_hist_scatter"}
    assert all(v == 0 for v in stats.values())


# ---------------------------------------------------------------------------
# 4. CLI contract: exit codes and one-line errors
# ---------------------------------------------------------------------------

def _tune_batch(tmp_path, mutate=None, name="batch.json"):
    s = uniform_scenario({"objective": "movement",
                          "space": {"dataflow": "all",
                                    "tile_vertices": [64, 128, 256]}})
    batch = {"scenarios": [s.to_dict()]}
    if mutate is not None:
        mutate(batch)
    path = tmp_path / name
    path.write_text(json.dumps(batch))
    return str(path)


def test_cli_tune_happy_path_writes_json(tmp_path, capsys):
    out = tmp_path / "BENCH_tune.json"
    rc = cli_main(["--tune", _tune_batch(tmp_path), "--json", str(out)])
    cap = capsys.readouterr()
    assert rc == 0
    assert "best_dataflow" in cap.out
    payload = json.loads(out.read_text())
    assert payload["status"] == "ok"
    t = payload["results"][0]["tune"]
    assert t["method"] == "exhaustive"
    assert t["best"]["feasible"] is True
    assert len(t["points"]) == t["n_evaluated"]


@pytest.mark.parametrize("mutate,msg", [
    (lambda b: b["scenarios"][0]["optimize"]["space"].update(bogus=[1]),
     "unknown optimize space axis"),
    (lambda b: b["scenarios"][0]["optimize"].update(
        budget={"sram_bits": -1}), "negative SRAM budget"),
    (lambda b: b["scenarios"][0]["optimize"].update(
        objective={"movement": float("inf")}), "non-finite objective weight"),
    (lambda b: b["scenarios"][0].pop("optimize"), "no 'optimize' block"),
], ids=["unknown-axis", "negative-budget", "inf-weight", "plain-scenario"])
def test_cli_tune_schema_errors_exit_2(tmp_path, capsys, mutate, msg):
    rc = cli_main(["--tune", _tune_batch(tmp_path, mutate)])
    cap = capsys.readouterr()
    assert rc == 2
    err_lines = [ln for ln in cap.err.splitlines() if ln.startswith("error:")]
    assert len(err_lines) == 1 and msg in err_lines[0]


def test_cli_tune_infeasible_budget_exits_2(tmp_path, capsys):
    path = _tune_batch(tmp_path, lambda b: b["scenarios"][0]["optimize"]
                       .update(budget={"sram_bits": 1}))
    rc = cli_main(["--tune", path])
    cap = capsys.readouterr()
    assert rc == 2
    assert "below every explored configuration" in cap.err


def test_cli_tune_refuses_mode_mixing(tmp_path, capsys):
    rc = cli_main(["--tune", _tune_batch(tmp_path), "--template", "fig3"])
    cap = capsys.readouterr()
    assert rc == 2
    assert "cannot be combined" in cap.err


def test_cli_tune_pin_drift_exits_1(tmp_path, capsys):
    path = _tune_batch(
        tmp_path, lambda b: b["scenarios"][0].update(
            expect={"best_dataflow": "no-such-dataflow"}))
    rc = cli_main(["--tune", path])
    cap = capsys.readouterr()
    assert rc == 1
    assert "GOLDEN DRIFT" in cap.err


@pytest.mark.parametrize("argv,msg", [
    (["--scenario", "{tmp}/no-such-file.json"], "error:"),
    (["--scenario", "{tmp}/invalid.json"], "error:"),
    (["--scenario", "{tmp}/unknown-key.json"], "error:"),
    (["--scenario", "{tmp}/unknown-dataflow.json"], "error:"),
    (["--scenario", "{tmp}/bad-expect.json"], "error:"),
    ([], "no scenarios given"),
], ids=["missing-file", "invalid-json", "unknown-scenario-key",
        "unknown-dataflow", "bad-expect-key", "no-sources"])
def test_cli_scenario_error_paths_exit_2(tmp_path, capsys, argv, msg):
    (tmp_path / "invalid.json").write_text("{not json")
    tile = Scenario.tile(ALL_DATAFLOWS[0]).to_dict()
    (tmp_path / "unknown-key.json").write_text(
        json.dumps({"scenarios": [{**tile, "frobnicate": 1}]}))
    (tmp_path / "unknown-dataflow.json").write_text(
        json.dumps({"scenarios": [{**tile, "dataflow": "no-such"}]}))
    (tmp_path / "bad-expect.json").write_text(
        json.dumps({"scenarios": [{**tile, "expect": {"bogus_key": 1.0}}]}))
    rc = cli_main([a.format(tmp=tmp_path) for a in argv])
    cap = capsys.readouterr()
    assert rc == 2
    assert msg in cap.err


def test_cli_scenario_pin_drift_exits_1(tmp_path, capsys):
    tile = Scenario.tile(ALL_DATAFLOWS[0]).to_dict()
    path = tmp_path / "drift.json"
    path.write_text(json.dumps(
        {"scenarios": [{**tile, "expect": {"total_bits": 123.0}}]}))
    rc = cli_main(["--scenario", str(path)])
    cap = capsys.readouterr()
    assert rc == 1
    assert "GOLDEN DRIFT" in cap.err
