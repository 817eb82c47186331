"""Algorithm 2 — the full LayerMerge procedure, plus the two baselines.

``compress(host, ...)`` runs: build tables → DP (Algorithm 1) → replace →
(optionally fine-tune) → merge.  ``method``:

* ``'layermerge'`` — the paper's joint optimization (activations + layers);
* ``'depth'``      — Kim et al. 2023 baseline: activations only (C = [L]);
* ``'layeronly'``  — whole-layer knapsack (Problem 8), no merging.

All per-layer probes (the ``T_orig`` pass and the knapsack's latency
column) route through :mod:`repro.core.probe_engine`, so they share the
same shape-signature bucketing as the table build instead of re-timing
every layer ad hoc.

The merge step itself lives in the runtime layer: results are
artifact-backed (``CompressResult.save(path)`` lowers the plan via
``host.lower_plan`` and publishes a portable merged-model artifact that
``repro.runtime.load`` reopens anywhere — serving, benchmarks,
fine-tuning).  ``python -m repro.compress`` wraps the whole pipeline in
one command.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable

from . import probe_engine
from .dp import DPResult, solve_dp, solve_knapsack
from .importance import ImportanceSpec, measure_importance
from .latency import AnalyticTPUOracle, LatencyOracle, WallClockOracle
from .plan import CompressionPlan, Segment
from .tables import Tables, build_tables, one_segment_plan


def _resolve_oracle(latency_oracle) -> LatencyOracle:
    """THE oracle-default resolution point — resolved once per pipeline
    run and threaded through :class:`CompressResult`, so the artifact can
    record which oracle certified its latency numbers."""
    return latency_oracle or AnalyticTPUOracle()


@dataclasses.dataclass
class CompressResult:
    plan: CompressionPlan
    tables: Tables | None
    original_latency: float
    compressed_latency: float
    dp_seconds: float
    oracle: LatencyOracle | None = None   # the resolved latency oracle
    host: object = None                   # the host that planned (for lowering)
    params: object = None                 # params the plan was built against
    dist_report: object = None            # DistReport when the table build
    #                                       fanned out across workers
    num_quarantined: int = 0              # probe buckets that fell back to
    #                                       the analytic estimate (T_orig
    #                                       pass + table build)

    @property
    def speedup(self) -> float:
        return self.original_latency / max(self.compressed_latency, 1e-12)

    # -- artifact export -------------------------------------------------------
    def lower(self):
        """Lower the plan to the shared unit IR (merged, deployable form)."""
        return self.host.lower_plan(self.plan, self.params)

    def save(self, path: str, extra_meta: dict | None = None) -> str:
        """Publish a portable merged-model artifact (see
        :mod:`repro.runtime.artifact`).  Records the plan, the merged
        unit graph + weights, the certifying oracle, and the measured
        latency numbers.  Returns the artifact's content fingerprint."""
        from repro import runtime
        from . import table_cache

        meta = {
            "oracle": (table_cache.oracle_token(self.oracle)
                       if self.oracle is not None else None),
            "original_latency": self.original_latency,
            "compressed_latency": self.compressed_latency,
            "predicted_speedup": self.speedup,
            "method": self.plan.method,
            "quantized_units": sum(1 for s in self.plan.segments
                                   if s.quant != "none"),
            # Latency entries that were NOT clean first-shot measurements
            # ("retimed"/"quarantined") — deployers can see exactly which
            # numbers the plan rests on (empty list: all clean).
            "probe_provenance": (
                [{"i": i, "j": j, "k": k, "flag": flag}
                 for (i, j, k), flag
                 in sorted(self.tables.provenance.items())]
                if self.tables is not None else []),
        }
        meta.update(extra_meta or {})
        return runtime.save(path, self.lower(), plan=self.plan, meta=meta)


def original_latency(host, latency_oracle=None, params=None, *,
                     engine: str = "batched") -> float:
    """Σ per-layer latency of the untouched network (the paper's T_orig)."""
    oracle = _resolve_oracle(latency_oracle)
    return sum(probe_engine.layer_latencies(host, oracle, params,
                                            engine=engine))


def compress(
    host,
    *,
    budget_ratio: float,
    P: int = 200,
    method: str = "layermerge",
    latency_oracle: LatencyOracle | None = None,
    importance: ImportanceSpec | str = "magnitude",
    base_perf: float | None = None,
    params=None,
    engine: str = "batched",
    cache_dir: str | None = None,
    probe_config: probe_engine.ProbeConfig | None = None,
    resume: bool = True,
    workers: int = 0,
    host_spec: dict | None = None,
    work_dir: str | None = None,
    quantize: str | None = None,
) -> CompressResult | None:
    """Run LayerMerge (or a baseline) at ``T0 = budget_ratio · T_orig``.

    The result is artifact-backed: it carries the host, params, and the
    resolved oracle, so ``result.save(path)`` publishes a portable
    merged-model artifact without re-deriving any of them.

    ``probe_config`` / ``resume`` are the crash-safety knobs threaded to
    :func:`repro.core.tables.build_tables`: probe retry/timeout/
    quarantine policy, and journal-based resumption of an interrupted
    table build (requires ``cache_dir``).

    ``workers > 0`` fans the latency probes out across subprocess workers
    (:func:`repro.core.dist_build.dist_build_tables` — requires
    ``cache_dir`` plus a ``host_spec`` naming a factory that rebuilds
    this host in another process); the fan-out's :class:`DistReport`
    lands on ``result.dist_report``.  The merged tables are bit-identical
    to ``workers=0``, so every downstream number is unchanged.

    ``quantize`` ('int8' | 'w8a8') widens every span's candidate row with
    derived precision siblings (:func:`repro.core.tables.
    quant_sibling_entries`), so the DP co-optimizes merge structure ×
    per-unit precision under the one budget; segments it picks quantized
    lower to narrow-weight units.  ``None``/'none' leaves tables, DP
    visit order, and plans bit-identical to an fp-only run.
    """
    oracle = _resolve_oracle(latency_oracle)
    if workers > 0:
        from .dist_build import DistBuildError, check_fanout

        check_fanout(oracle)
        if cache_dir is None:
            raise DistBuildError(
                "workers > 0 requires cache_dir (worker results merge "
                "through the build journal)")
    layer_stats = probe_engine.EngineStats(engine=engine)
    layer_lats = probe_engine.layer_latencies(host, oracle, params,
                                              engine=engine,
                                              stats=layer_stats,
                                              probe_config=probe_config)
    t_orig = sum(layer_lats)
    T0 = budget_ratio * t_orig
    L = len(host.descs())

    if method == "layeronly":
        if quantize and quantize != "none":
            raise ValueError("quantize is a merged-segment feature; "
                             "method='layeronly' has no merged units")
        res = _layer_only(host, T0, P, oracle, importance, base_perf,
                          params, t_orig, layer_lats)
        if res is not None:
            res.num_quarantined = layer_stats.num_quarantined
        return res

    dist_report = None
    if workers > 0:
        from .dist_build import dist_build_tables

        tables, dist_report = dist_build_tables(
            host, cache_dir=cache_dir, workers=workers,
            host_spec=host_spec, method=method, latency_oracle=oracle,
            importance=importance, base_perf=base_perf, params=params,
            engine=engine, probe_config=probe_config, resume=resume,
            work_dir=work_dir)
        # Precision siblings are derived AFTER the distributed merge: the
        # worker manifest/journal stay fp-only, so fan-out bit-identity
        # (and resume) are untouched by quantization.
        from .tables import with_quant_siblings
        tables = with_quant_siblings(tables, host, quantize)
    else:
        tables = build_tables(host, method=method, latency_oracle=oracle,
                              importance=importance, base_perf=base_perf,
                              params=params, engine=engine,
                              cache_dir=cache_dir,
                              probe_config=probe_config, resume=resume,
                              quantize=quantize)
    t0 = time.perf_counter()
    res = solve_dp(L, tables.fn(), T0, P, method=method,
                   original_k=host.original_k)
    dp_s = time.perf_counter() - t0
    if res is None:
        return None
    quarantined = layer_stats.num_quarantined + (
        tables.stats.num_quarantined if tables.stats is not None else 0)
    return CompressResult(plan=res.plan, tables=tables,
                          original_latency=t_orig,
                          compressed_latency=res.latency,
                          dp_seconds=dp_s, oracle=oracle, host=host,
                          params=params, dist_report=dist_report,
                          num_quarantined=quarantined)


def _layer_only(host, T0, P, oracle, importance, base_perf, params, t_orig,
                layer_lats):
    """Problem 8: latency-aware layer pruning (knapsack).

    ``layer_lats`` comes from the caller's probe pass — the same engine
    walk that produced ``T_orig`` — so each layer is probed exactly once.
    """
    descs = host.descs()
    L = len(descs)
    imp: dict[int, float] = {}
    lat: dict[int, float] = dict(zip(range(1, L + 1), layer_lats))
    forced = tuple(d.index for d in descs if not d.prunable)
    total = sum(d.value for d in descs) or 1.0
    for l in range(1, L + 1):
        # I[l] — importance of KEEPING l: exp(perf drop when l is removed).
        if not descs[l - 1].prunable:
            imp[l] = 1.0
        elif isinstance(importance, ImportanceSpec):
            probe = Segment(i=l - 1, j=l, k=host.pruned_k(l), kept=())
            apply_fn, p = host.replaced_apply(
                one_segment_plan(host, probe), params)
            removed = measure_importance(apply_fn, p, importance,
                                         base_perf or 0.0)
            imp[l] = 1.0 / max(removed, 1e-12)
        else:
            imp[l] = math.exp(descs[l - 1].value / total)
    t0 = time.perf_counter()
    sol = solve_knapsack(L, imp, lat, T0, P, forced=forced)
    dp_s = time.perf_counter() - t0
    if sol is None:
        return None
    C, obj, true_lat = sol
    kept = set(C)
    segs = tuple(
        Segment(i=l - 1, j=l,
                k=host.original_k(l) if l in kept else host.pruned_k(l),
                kept=(l,) if l in kept else (),
                original=l in kept)
        for l in range(1, L + 1))
    plan = CompressionPlan(num_layers=L, segments=segs, objective=obj,
                           latency=true_lat, budget=T0, method="layeronly")
    return CompressResult(plan=plan, tables=None, original_latency=t_orig,
                          compressed_latency=true_lat, dp_seconds=dp_s,
                          oracle=oracle, host=host, params=params)
