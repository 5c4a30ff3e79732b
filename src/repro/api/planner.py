"""Planner: pick a driver AND a stage order, explainably.

``Database.search`` routes every query batch through ``plan_search``,
which inspects what the session actually has — a stage-0 index, an
attached mesh, the database/query shapes — and picks the scan / host /
indexed / sharded pipeline.  The decision is deterministic and cheap
(no measurement, no state), and :meth:`Plan.explain` prints the chosen
driver, the stage list straight from ``repro.core.pipeline.PIPELINES``,
and the reasons, so "why did my query take this path" is one call.

Since the bound family became pluggable (LB_Kim before the envelope
stages, LB_Webb after LB_Keogh — ``repro.core.lb``), *which stages to
run in which order* is a second planning axis.  The paper answers it
analytically for the fixed pair LB_Keogh -> LB_Improved; here the
answer comes from data: ``calibrate`` runs every registered bound over
a small probe sample at ``Database.build`` time (a few rows as stand-in
queries against a candidate subsample, plus their true banded DTWs),
and ``choose_cascade`` simulates each registered pipeline over those
measurements — per-stage survivor fractions against the sample's k-th
best distance, times analytic per-stage unit costs — and picks the
cheapest predicted cascade (``method="auto"``).  Every candidate
pipeline ends in the exact DP and every bound is sound (tier-1's
``test_bound_soundness``), so the choice affects *cost only*: any
chosen cascade returns bit-identical top-k values and indices.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.pipeline import PIPELINES
from repro.api.config import SearchConfig

#: planner-eligible drivers and the entry point each routes to.
DRIVERS = {
    "scan": "repro.core.cascade.nn_search_scan",
    "host": "repro.core.cascade.nn_search_host",
    "indexed": "repro.core.cascade.nn_search_indexed",
    "sharded": "repro.core.distributed.sharded_nn_search",
    "anytime": "repro.anytime.search.anytime_search",
    "subsequence": "repro.anytime.search.exact_subsequence_search",
}

#: below this many candidate rows the jitted device scan beats the
#: host-orchestrated survivor compaction (per-block python overhead
#: dominates tiny sweeps); measured on the FAST bench sizes.
SMALL_DB_ROWS = 1024

#: LB stages the calibration probe measures, in tightness order.
CALIBRATED_STAGES = ("lb_kim", "lb_keogh", "lb_improved", "lb_webb")

#: analytic per-candidate unit costs, in units of one O(n) elementwise
#: sweep over the series: LB_Kim reads four scalars per lane (well under
#: a sweep, but the lane still pays dispatch + load); LB_Keogh is one
#: clamp-project-accumulate pass; LB_Improved pass 2 builds a
#: per-(query, candidate) envelope on top of pass 1; LB_Webb adds the
#: candidate envelope + two-sided correction to pass 1.  The exact DP
#: costs one band row per sample: ``2w + 1`` sweeps (``full_dp_cost``).
#: These are the *fallback* costs: a session built with ``tune=...``
#: carries measured per-stage costs in the same units
#: (``repro.kernels.tuning.measure_stage_costs``), which override this
#: table stage-by-stage via ``choose_cascade(unit_costs=...)`` —
#: ``CascadePlan.explain()`` says which source each stage used.
STAGE_UNIT_COST = {
    "lb_kim": 1.0,
    "lb_keogh": 3.0,
    "lb_improved": 8.0,
    "lb_webb": 9.0,
    # TC-DTW stages (repro.mv.tc): tc_box reduces each lane to O(d*S)
    # scalars after shared reductions — well under one sweep; tc_tri is
    # O(R) arithmetic per lane, cheaper still
    "tc_box": 0.6,
    "tc_tri": 0.4,
}


def full_dp_cost(w: int) -> float:
    """Banded-DP cost per candidate, in O(n)-sweep units: one band row
    of ``2w + 1`` cells per series sample."""
    return 2.0 * float(w) + 1.0


@dataclasses.dataclass(frozen=True)
class Calibration:
    """Measured probe: every registered bound over a (q, c) row sample.

    ``bounds[s, i, j]`` is the powered ``stage_names[s]`` bound between
    probe query ``i`` and sampled candidate ``j``; ``dtw[i, j]`` the
    true powered banded DTW.  Built once at ``Database.build``
    (``calibrate``), persisted in the bundle, consumed by
    ``choose_cascade`` — planning never re-measures.
    """

    stage_names: tuple[str, ...]
    bounds: np.ndarray  # (S, q, c) powered stage bounds
    dtw: np.ndarray  # (q, c) powered banded DTW
    w: int  # band the probe ran at (pins full_dp_cost)

    def to_arrays(self) -> dict[str, np.ndarray]:
        """Bundle serialization (``cal_*`` keys in ``Database.save``)."""
        return {
            "stage_names": np.asarray(self.stage_names),
            "bounds": self.bounds,
            "dtw": self.dtw,
            "w": np.int64(self.w),
        }

    @classmethod
    def from_arrays(cls, arrays: dict[str, np.ndarray]) -> "Calibration":
        return cls(
            stage_names=tuple(str(s) for s in arrays["stage_names"]),
            bounds=np.asarray(arrays["bounds"], np.float64),
            dtw=np.asarray(arrays["dtw"], np.float64),
            w=int(arrays["w"]),
        )


def calibrate(
    rows: np.ndarray,
    w: int,
    p,
    sample_q: int = 4,
    sample_c: int = 128,
    d: int = 1,
) -> Calibration:
    """Measure every registered bound on a small sample of ``rows``.

    Evenly-spaced rows stand in for queries (``sample_q`` of them)
    against an evenly-spaced candidate subsample (``sample_c``); all
    four powered bounds plus the true powered DTW are computed for every
    probe pair.  Cost is O(sample_q * sample_c) bound evaluations plus
    as many banded DPs — for the defaults, 512 pairs, a once-per-build
    blip next to the stage-0 index.

    ``d > 1`` probes the multivariate forms on channel-major flattened
    rows and additionally measures the ``tc_box`` stage, making the
    ``"tc_box"`` pipeline eligible under ``method="auto"``; at ``d = 1``
    the probe (and hence every auto choice) is exactly the univariate
    one — no tc stage appears, so univariate sessions keep their
    pre-mv cascade decisions bit for bit.
    """
    import jax.numpy as jnp

    from repro.core import lb as lb_mod
    from repro.mv import tc as tc_mod
    from repro.mv.dtw import dtw_qbatch_mv
    from repro.mv.envelope import envelope_batch_mv
    from repro.mv.lb import (
        lb_improved_mv_powered_qbatch,
        lb_webb_mv_powered_qbatch,
    )

    n_db = rows.shape[0]
    qi = np.unique(
        np.linspace(0, n_db - 1, min(sample_q, n_db)).astype(np.int64)
    )
    ci = np.unique(
        np.linspace(0, n_db - 1, min(sample_c, n_db)).astype(np.int64)
    )
    qs = jnp.asarray(rows[qi])
    cs = jnp.asarray(rows[ci])
    upper, lower = envelope_batch_mv(qs, w, d)
    rows_b = [
        np.asarray(lb_mod.lb_kim_powered_qbatch(cs, qs, p), np.float64),
        np.asarray(
            lb_mod.lb_keogh_powered_qbatch(cs, upper, lower, p),
            np.float64,
        ),
        np.asarray(
            lb_improved_mv_powered_qbatch(cs, qs, upper, lower, w, p, d),
            np.float64,
        ),
        np.asarray(
            lb_webb_mv_powered_qbatch(cs, qs, upper, lower, w, p, d),
            np.float64,
        ),
    ]
    names = CALIBRATED_STAGES
    if d > 1:
        names = names + ("tc_box",)
        rows_b.append(
            np.asarray(
                tc_mod.tc_box_powered_qbatch(cs, upper, lower, p, d),
                np.float64,
            )
        )
    bounds = np.stack(rows_b)
    dtw = np.asarray(
        dtw_qbatch_mv(qs, cs, w, p, powered=True, d=d), np.float64
    )
    return Calibration(names, bounds, dtw, int(w))


@dataclasses.dataclass(frozen=True)
class CascadePlan:
    """One stage-order decision: the chosen pipeline + its cost model.

    ``enter_frac[j]`` is the predicted fraction of candidates that
    reach ``stages[j]`` (survivors of every earlier bound at the probe
    sample's k-th best threshold); ``stage_cost[j]`` the per-candidate
    unit cost of running it; ``cost_per_candidate`` their dot product —
    the objective ``choose_cascade`` minimized.  ``predicted`` maps
    every candidate pipeline to its predicted cost, so "why not X" is
    answered by the same object.
    """

    method: str  # the chosen PIPELINES key
    stages: tuple[str, ...]
    enter_frac: tuple[float, ...]
    stage_cost: tuple[float, ...]
    cost_per_candidate: float
    k: int
    predicted: tuple[tuple[str, float], ...]  # (method, cost), sorted
    #: per-stage cost provenance, "measured" (tune sweep) or "analytic"
    #: (STAGE_UNIT_COST / full_dp_cost); empty on pre-tuning plans
    cost_source: tuple[str, ...] = ()

    def explain(self) -> str:
        lines = [
            f"cascade: {' -> '.join(self.stages)} (method={self.method}, "
            f"calibrated at k={self.k})",
            f"predicted cost/candidate: {self.cost_per_candidate:.2f} "
            f"O(n)-sweep units",
        ]
        src = self.cost_source or ("analytic",) * len(self.stages)
        measured = sorted({s for s, o in zip(self.stages, src) if o == "measured"})
        lines.append(
            "unit costs: measured by the kernel tune sweep for "
            + ", ".join(measured)
            + ("; analytic elsewhere" if len(measured) < len(set(self.stages)) else "")
            if measured
            else "unit costs: analytic (no tune sweep measured)"
        )
        for s, f, c, o in zip(self.stages, self.enter_frac, self.stage_cost, src):
            lines.append(
                f"  {s:<12} enter {100 * f:6.2f}%  unit cost {c:5.1f} "
                f"[{o}]  -> {f * c:6.2f}"
            )
        others = ", ".join(
            f"{m}={c:.2f}" for m, c in self.predicted if m != self.method
        )
        if others:
            lines.append(f"rejected: {others}")
        return "\n".join(lines)


def choose_cascade(
    cal: Calibration, k: int = 1, methods=None, unit_costs=None
) -> CascadePlan:
    """Pick the cheapest predicted stage order from the calibration.

    For each candidate pipeline the probe sample is pushed through its
    stages: a pair survives stage ``s`` iff ``bound_s < t_i`` where
    ``t_i`` is probe query ``i``'s k-th smallest sampled powered DTW
    (the cascade's steady-state pruning threshold).  Predicted cost per
    candidate is ``sum_j unit_cost_j * enter_frac_j`` plus the banded
    DP on whatever survives every bound.  Deterministic: ties break on
    (cost, stage count, name).

    ``unit_costs``, when given, is a mapping of stage name (and/or
    ``"full"``) to a *measured* per-candidate cost in the same
    O(n)-sweep units (a tune sweep's ``measure_stage_costs``); measured
    entries override the analytic table stage-by-stage, and the
    returned plan records which source each stage used
    (``cost_source``).
    """
    if methods is None:
        methods = sorted(
            m
            for m, stages in PIPELINES.items()
            if all(s in cal.stage_names or s == "full" for s in stages)
        )
    unit_costs = unit_costs or {}
    bound_of = {s: cal.bounds[i] for i, s in enumerate(cal.stage_names)}
    kk = min(int(k), cal.dtw.shape[1])
    thr = np.sort(cal.dtw, axis=1)[:, kk - 1][:, None]  # (q, 1)

    def stage_cost(s):
        if s in unit_costs:
            return float(unit_costs[s]), "measured"
        if s == "full":
            return full_dp_cost(cal.w), "analytic"
        return STAGE_UNIT_COST[s], "analytic"

    scored = []
    for m in methods:
        stages = PIPELINES[m]
        alive = np.ones_like(cal.dtw, dtype=bool)
        fracs, costs, srcs = [], [], []
        for s in stages:
            fracs.append(float(alive.mean()))
            c, src = stage_cost(s)
            costs.append(c)
            srcs.append(src)
            if s != "full":
                alive = alive & (bound_of[s] < thr)
        total = float(np.dot(fracs, costs))
        scored.append(
            (total, len(stages), m, tuple(fracs), tuple(costs), tuple(srcs))
        )
    scored.sort(key=lambda t: (t[0], t[1], t[2]))
    total, _, method, fracs, costs, srcs = scored[0]
    return CascadePlan(
        method=method,
        stages=PIPELINES[method],
        enter_frac=fracs,
        stage_cost=costs,
        cost_per_candidate=total,
        k=kk,
        predicted=tuple(
            (m, t) for t, _, m, _, _, _ in sorted(scored, key=lambda t: t[0])
        ),
        cost_source=srcs,
    )


@dataclasses.dataclass(frozen=True)
class Plan:
    """One routing decision: driver + stage order + why.

    ``mode``/``budget`` carry the anytime-tier decision (DESIGN.md
    §3.10): ``mode="anytime"`` routes through the budgeted best-first
    cluster explorer, where answer *quality*, not just cost, is
    planner-controlled.
    """

    driver: str  # a DRIVERS key
    stages: tuple[str, ...]  # cascade stages, stage-0 filters included
    reasons: tuple[str, ...]
    n_queries: int
    config: SearchConfig
    cascade: CascadePlan | None = None  # set when the planner chose the order
    mode: str = "exact"  # "exact" | "anytime"
    budget: int | None = None  # refined windows per query; None = unlimited
    channels: int = 1  # data channel count d (DESIGN.md §3.12)

    def _mv_considered(self) -> tuple[str, ...]:
        """TC-DTW stages this plan actually weighed: stages in the chosen
        pipeline, plus (under method="auto") stages in any pipeline the
        calibrated chooser scored."""
        seen = {s for s in self.stages if s in ("tc_box", "tc_tri")}
        if self.cascade is not None:
            for m, _cost in self.cascade.predicted:
                seen |= {
                    s for s in PIPELINES[m] if s in ("tc_box", "tc_tri")
                }
        return tuple(sorted(seen))

    def explain(self) -> str:
        mv = self._mv_considered()
        lines = [
            f"driver: {self.driver} ({DRIVERS[self.driver]})",
            f"stages: {' -> '.join(self.stages)}",
            f"queries: {self.n_queries} (method={self.config.method}, "
            f"p={self.config.p}, k={self.config.k}, "
            f"block={self.config.block})",
            f"channels: {self.channels}"
            + (
                f" (mv stages considered: {', '.join(mv)})"
                if mv
                else " (mv stages considered: none)"
            ),
        ]
        if self.mode == "anytime":
            budget = (
                "unlimited (answers are exact)"
                if self.budget is None
                else f"{self.budget} refined windows/query"
            )
            lines.append(
                f"mode: anytime — best-so-far top-k with sound error "
                f"bounds; budget {budget}"
            )
        lines.append("because:")
        lines += [f"  - {r}" for r in self.reasons]
        if self.cascade is not None:
            lines.append(self.cascade.explain())
        return "\n".join(lines)


def plan_search(
    config: SearchConfig,
    n_rows: int,
    n_queries: int,
    *,
    has_index: bool,
    has_mesh: bool,
    driver: str | None = None,
    cascade: CascadePlan | None = None,
    mode: str = "exact",
    budget: int | None = None,
    anytime_info: dict | None = None,
    channels: int = 1,
) -> Plan:
    """Choose the pipeline for a query batch against one database session.

    Priority: an explicit ``driver`` override wins; then the stage-0
    index (the most specific prebuilt artifact); then an attached mesh
    (the caller asked for sharded serving); then scan-vs-host on the
    database size and stage structure.  ``cascade`` carries the
    calibration-driven stage-order decision when the session resolved
    ``method="auto"`` (``Database._resolve_method``) — it rides the
    plan so ``explain()`` shows *both* axes of the decision.

    ``mode="anytime"`` (and exact subsequence queries, signalled by
    ``anytime_info["subsequence"]``) routes through the anytime tier
    instead: ``anytime_info`` summarizes the tier (lengths, windows,
    clusters) for the explanation.
    """
    if mode not in ("exact", "anytime"):
        raise ValueError(
            f"mode={mode!r} unknown; use 'exact' or 'anytime'"
        )
    stages = PIPELINES[config.method]
    cascade_reason = (
        (
            f"stage order chosen by calibration: method="
            f"{config.method!r} predicts "
            f"{cascade.cost_per_candidate:.2f} sweep units/candidate",
        )
        if cascade is not None
        else ()
    )
    if mode == "anytime" or (anytime_info or {}).get("subsequence"):
        if anytime_info is None:
            raise ValueError(
                "mode='anytime' needs the anytime tier: build the session "
                "with Database.build(..., anytime=True) (or a dict of "
                "tier options)"
            )
        if driver is not None:
            raise ValueError(
                f"driver={driver!r} cannot be combined with the anytime "
                f"tier — the cluster explorer is the driver"
            )
        info = (
            f"{anytime_info.get('windows', '?')} windows in "
            f"{anytime_info.get('clusters', '?')} clusters at lengths "
            f"{anytime_info.get('lengths', '?')}"
        )
        if mode == "anytime":
            return Plan(
                "anytime",
                ("cluster_lb",) + stages,
                (
                    f"anytime tier: best-first exploration over {info}; "
                    f"cluster bounds from envelope boxes + the Theorem 1 "
                    f"triangle inequality, refinement through the "
                    f"standard stage pipeline",
                )
                + cascade_reason,
                n_queries,
                config,
                cascade,
                mode="anytime",
                budget=budget,
                channels=channels,
            )
        if budget is not None:
            raise ValueError(
                "budget= only applies to mode='anytime' (exact search "
                "always explores everything)"
            )
        return Plan(
            "subsequence",
            stages,
            (
                f"subsequence query (length != whole-row length): exact "
                f"gid-order sweep over the anytime tier's window bank "
                f"({info})",
            )
            + cascade_reason,
            n_queries,
            config,
            channels=channels,
        )
    if budget is not None:
        raise ValueError(
            "budget= only applies to mode='anytime' (exact search always "
            "explores everything)"
        )
    if driver is not None:
        if driver in ("anytime", "subsequence"):
            raise ValueError(
                f"driver={driver!r} is not directly selectable: use "
                f"mode='anytime' (or a subsequence-length query) on a "
                f"session built with anytime=True"
            )
        if driver not in DRIVERS:
            raise ValueError(
                f"driver={driver!r} unknown; available: {sorted(DRIVERS)}"
            )
        if driver == "indexed" and not has_index:
            raise ValueError(
                "driver='indexed' but no stage-0 index is built: pass "
                "index=True to Database.build (or load a bundle saved "
                "with one)"
            )
        if driver == "sharded" and not has_mesh:
            raise ValueError(
                "driver='sharded' but no mesh is attached: call "
                "Database.use_mesh(mesh) first"
            )
        if driver == "indexed":
            stages = ("lb_tri",) + stages
        return Plan(
            driver,
            stages,
            ("caller override",) + cascade_reason,
            n_queries,
            config,
            cascade,
            channels=channels,
        )

    if has_index:
        return Plan(
            "indexed",
            ("lb_tri",) + stages,
            (
                "stage-0 triangle index built for this database: O(R) "
                "arithmetic per candidate kills most lanes before any "
                "envelope work, and the reference distances seed the "
                "top-k exactly",
            )
            + cascade_reason,
            n_queries,
            config,
            cascade,
            channels=channels,
        )
    if has_mesh:
        return Plan(
            "sharded",
            stages,
            (
                "mesh attached via Database.use_mesh: the database is "
                "sharded over its devices and per-query best bounds are "
                "min-exchanged between block rounds",
            )
            + cascade_reason,
            n_queries,
            config,
            cascade,
            channels=channels,
        )
    if config.method == "full":
        return Plan(
            "scan",
            stages,
            (
                "method='full' has no LB stages to compact, so the dense "
                "jitted block scan is the fastest layout",
            )
            + cascade_reason,
            n_queries,
            config,
            cascade,
            channels=channels,
        )
    if n_rows < SMALL_DB_ROWS:
        return Plan(
            "scan",
            stages,
            (
                f"database has {n_rows} rows (< {SMALL_DB_ROWS}): one "
                f"jitted device sweep beats host orchestration overhead "
                f"at this size",
            )
            + cascade_reason,
            n_queries,
            config,
            cascade,
            channels=channels,
        )
    return Plan(
        "host",
        stages,
        (
            f"database has {n_rows} rows (>= {SMALL_DB_ROWS}): the host "
            f"driver gathers LB survivors into pooled fixed-size DP "
            f"chunks, so post-LB wall-clock tracks surviving work "
            f"(the driver benchmarked against the paper's figures)",
        )
        + cascade_reason,
        n_queries,
        config,
        cascade,
        channels=channels,
    )
