"""Capacity sweeps on the device schedule engine, in a closed loop.

Traffic parameters: ``octaves``, ``engine`` and ``metric``, the name
the window's time per capacity is reported under. The first octave's
capacity is its floor ``2**k``: the smallest capacity has the most tiles
and so fixes the padded tile axis of the engine's one compiled program,
which every seed then shares. For each further octave one capacity is
drawn log-uniform from ``[2**k, 2**(k+1))``, from the seed, once per run.

Each sweep is one ``GraphTrace.schedules(caps, engine=...)`` call after
the per-trace schedule cache has been dropped, with the program's
schedule store switched off, so every capacity of every sweep is
computed. The window runs whole sweeps and stops at the first sweep
boundary past ``seconds``; the window over the capacities completed is
the traffic's ``metric``. The wall time of every sweep is noted as
``sweep_ms``, one entry per sweep, each from the end of the one before,
so the entries add up to the window.

Correct: the vertex, edge, halo and remote-edge counts of the window's
schedules (every sweep on the products graph, a seeded sample of sweeps
where there are thousands) equal the plain reference's, computed from the
benchmark's own copy of the graph; and the trace counters show every
capacity computed, none served from a cache.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import graphs
import reference

#: Sweeps whose results are kept for the comparison (a seeded reservoir
#: sample, plus the last sweep).
KEEP_SWEEPS = 64


def draw_capacities(seed: int, octaves) -> list[int]:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xCA9]))
    first, *rest = octaves
    return [2 ** first] + [int(2.0 ** (k + rng.random())) for k in rest]


class Driver:
    def __init__(self, ctx) -> None:
        from repro.core import trace as rtrace

        self._rtrace = rtrace
        self.ctx = ctx
        self.cfg = ctx.config
        self.engine = ctx.traffic["engine"]
        self.metric = ctx.traffic["metric"]
        self.data_dir = ctx.data_dir
        self.caps = draw_capacities(ctx.seed, ctx.traffic["octaves"])
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(ctx.seed), 0x5A3]))
        t0 = time.perf_counter()
        name, params = graphs.register_trace(self.cfg, ctx.data_dir)
        self.trace = rtrace.resolve_trace_dataset(name, params)
        ctx.note("resolve_s", time.perf_counter() - t0)
        # From here on no schedule is read from or written to the store.
        os.environ["REPRO_TRACE_CACHE"] = "off"
        # The smallest capacity fixes the padded tile axis of the whole
        # sweep, so it alone warms every program.
        t0 = time.perf_counter()
        self.compute([min(self.caps)])
        self.trace.clear_schedules()
        ctx.note("warmup_s", time.perf_counter() - t0)
        ctx.note("capacities", self.caps)
        ctx.note("n_tiles", [reference.geometry(self.cfg["n_nodes"], c)[0]
                             for c in self.caps])
        ctx.note("unique_pairs", int(self.trace.n_edges))

    def compute(self, caps):
        """The system under test: one batched schedule call."""
        return self.trace.schedules(caps, engine=self.engine)

    def window(self, seconds: float) -> dict:
        import jax

        stats0 = dict(self._rtrace.trace_cache_info()["stats"])
        kept, sweep_s = [], []
        t0 = t_prev = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.sweep.clear"):
                self.trace.clear_schedules()
            with jax.profiler.TraceAnnotation("bench.sweep.schedules"):
                result = self.compute(self.caps)
            if len(kept) < KEEP_SWEEPS:
                kept.append(result)
            else:
                j = int(self.rng.integers(0, len(sweep_s) + 1))
                if j < KEEP_SWEEPS:
                    kept[j] = result
            t = time.perf_counter()
            sweep_s.append(t - t_prev)
            t_prev = t
            if t - t0 >= seconds:
                break
        elapsed = t - t0
        kept.append(result)
        stats1 = self._rtrace.trace_cache_info()["stats"]
        delta = {k: stats1[k] - stats0[k] for k in stats1}
        n = len(sweep_s) * len(self.caps)
        self.ctx.note("sweep_ms", [round(s * 1e3, 3) for s in sweep_s])
        return {"metrics": {self.metric: elapsed / n * 1e3},
                "attempted": n, "elapsed_s": elapsed, "sweeps": len(sweep_s),
                "capacities": n, "unique_pairs": int(self.trace.n_edges),
                "stats": delta, "kept": kept}

    def release(self) -> None:
        self._rtrace.clear_trace_cache()
        self.trace = None

    def verify(self, record: dict):
        snd, rcv = graphs.graph_arrays(self.cfg, self.data_dir)
        n = self.cfg["n_nodes"]
        with ThreadPoolExecutor(len(self.caps)) as pool:
            refs = dict(zip(self.caps, pool.map(
                lambda c: reference.tile_counts(snd, rcv, n, c), self.caps)))
        mismatches, failed = 0, 0
        for result in record["kept"]:
            for cap, sched in zip(self.caps, result):
                bad = count_mismatches(sched, refs[cap])
                mismatches += bad
                failed += bad > 0
        stats = record["stats"]
        hits = stats["schedule_cache_hits"] + stats["schedule_disk_hits"]
        missing = max(0, record["capacities"] - stats["schedule_computes"])
        checks = {
            "count_mismatches": {"value": mismatches, "limit": 0},
            "cache_hits_in_window": {"value": hits, "limit": 0},
            "capacities_not_computed": {"value": missing, "limit": 0},
        }
        return checks, failed


def count_mismatches(sched, ref: dict) -> int:
    """Entries of one schedule that differ from the reference (a wrong tile
    count or stride counts every entry of that capacity)."""
    total = sum(ref[f].size for f in reference.COUNT_FIELDS)
    if int(sched.n_tiles) != ref["n_tiles"] or int(sched.K) != ref["K"]:
        return total
    bad = 0
    for f in reference.COUNT_FIELDS:
        got = np.asarray(getattr(sched, f))
        if got.shape != ref[f].shape:
            bad += ref[f].size
        else:
            bad += int(np.count_nonzero(got != ref[f]))
    return bad
