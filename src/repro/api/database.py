"""Database: the build-once / query-many session facade (DESIGN.md §3.7).

The paper's whole pitch is amortization — spend a little once to skip
quadratic DTW work on every query — yet the low-level entry points
(``nn_search_scan`` / ``nn_search_host`` / ``nn_search_indexed`` /
``sharded_nn_search`` / ``StreamMatcher``) each re-derive per-database
artifacts per call and each take their own kwargs.  ``Database`` is the
index lifecycle those drivers were missing:

    cfg = SearchConfig(w=0, p="inf" and friends validated up front)
    db  = Database.build(data, cfg, index=True)   # build once
    db.plan(queries).explain()                    # see the routing
    res = db.search(queries)                      # query many
    db.save("session.npz"); Database.load(...)    # persist the bundle

``build`` computes every database-side artifact exactly once: the
(z-normalized, precision-cast) rows uploaded to device, their warping
envelopes, the float64 powered row norms (per-row scale in O(1) via
``row_mean_std``), and optionally the stage-0 triangle index.  Query-side
work (query envelopes, the cascade itself) stays lazy per call — it
depends on the query, not the database (tests/test_api_database.py pins
that a second ``search`` performs zero database-side envelope
recomputation).  ``search``/``topk``/``classify``/``stream`` all route
through the planner (``repro.api.planner``) onto the legacy drivers,
which remain public and bit-identical — the facade adds no numeric path
of its own, so every result is pinned to the corresponding low-level
call.
"""

from __future__ import annotations

import dataclasses
import os
import threading

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.config import SearchConfig
from repro.api.planner import (
    Calibration,
    CascadePlan,
    Plan,
    calibrate,
    choose_cascade,
    plan_search,
)
from repro.core.cascade import (
    BatchSearchResult,
    SearchResult,
    nn_search_host,
    nn_search_indexed,
    nn_search_scan,
)
from repro.index.build import TriangleIndex, build_index
from repro.index.store import index_arrays, index_from_arrays, npz_path
from repro.kernels.tuning import TuneTable, autotune_session, install
from repro.mv.envelope import envelope_batch_mv
from repro.mv.layout import flatten_channels
from repro.stream.state import STD_EPS

BUNDLE_FORMAT_VERSION = 1

#: rows per device call when ``build`` computes the database envelopes:
#: the device holds one chunk and its two envelopes at a time, never the
#: whole database (16384 rows x 1000 samples is 64 MiB of float32)
ENVELOPE_CHUNK_ROWS = 16384


def _znorm_rows(
    rows: np.ndarray, eps: float = STD_EPS, dtype="float32"
) -> np.ndarray:
    """Per-row global z-normalization, vectorized over rows.  For float32
    this is bit-identical to the stream scanner's ``znorm_series`` (the
    axis-1 reductions use the same pairwise summation over the same row
    bytes, same op order, same final cast — pinned by the facade parity
    tests); float64 keeps the full precision the session was configured
    for instead of round-tripping through f32."""
    x64 = np.asarray(rows, np.float64)
    mean = x64.mean(axis=1, keepdims=True)
    std = np.maximum(x64.std(axis=1, keepdims=True), eps)
    return ((x64 - mean) / std).astype(dtype)


def _require_x64_for(config: SearchConfig) -> None:
    """float64 artifacts are a lie unless JAX x64 is on — device ops
    would silently downcast; enforced at build *and* load."""
    if config.precision != "float64":
        return
    import jax

    if not jax.config.jax_enable_x64:
        raise ValueError(
            "precision='float64' needs JAX x64: set JAX_ENABLE_X64=1 (or "
            "jax.config.update('jax_enable_x64', True)) before "
            "building/loading; with x64 disabled device ops would "
            "silently downcast"
        )


def _envelopes_in_chunks(
    rows: np.ndarray, w: int, d: int
) -> tuple[np.ndarray, np.ndarray]:
    """Envelopes of every row, ``ENVELOPE_CHUNK_ROWS`` rows per device
    call, gathered on the host.  Rows are enveloped independently, so
    the chunking changes no value."""
    n_db = rows.shape[0]
    upper = np.empty_like(rows)
    lower = np.empty_like(rows)
    for lo in range(0, n_db, ENVELOPE_CHUNK_ROWS):
        hi = min(lo + ENVELOPE_CHUNK_ROWS, n_db)
        u, l = envelope_batch_mv(jnp.asarray(rows[lo:hi]), w, d)
        upper[lo:hi], lower[lo:hi] = np.asarray(u), np.asarray(l)
    return upper, lower


class Database:
    """One searchable time-series database session.

    Construct with :meth:`build` or :meth:`load`, never directly.  All
    artifacts are tied to the frozen :class:`SearchConfig` the session
    was built under; per-call overrides are limited to what cannot
    invalidate them (``k``, the driver choice, stream thresholds).
    """

    def __init__(
        self,
        *,
        raw: np.ndarray,
        data: np.ndarray,
        config: SearchConfig,
        w: int,
        upper: np.ndarray,
        lower: np.ndarray,
        row_sums: np.ndarray,
        row_sumsq: np.ndarray,
        index: TriangleIndex | None,
        calibration: Calibration | None = None,
        anytime=None,
        tune_table: TuneTable | None = None,
        d: int = 1,
    ):
        self.raw = raw  # as given (precision-cast), what save() persists
        # channel-major flattened (N, d*n) when d > 1, znormed per
        # (row, channel) when config.znorm; for d = 1 the univariate
        # rows exactly as before
        self.data = data
        self.d = int(d)  # channel count (DESIGN.md §3.12)
        self.config = config
        self.w = w  # resolved band half-width (config.w or n // 10)
        self.upper = upper  # (N, n) db-row envelopes at band w
        self.lower = lower
        # (N,) float64 powered norms of the raw rows (sum x, sum x^2):
        # cached so per-row scale is O(1) for callers (row_mean_std,
        # external calibration) instead of an O(N n) sweep per use; the
        # cascade itself never consumes them — its bounds are envelope-
        # based — so they ride the bundle as a serving-side artifact
        self.row_sums = row_sums
        self.row_sumsq = row_sumsq
        self.index = index
        # the anytime subsequence tier (repro.anytime.AnytimeIndex):
        # window banks + cluster trees per length of interest
        self.anytime = anytime
        # kernel tune table (DESIGN.md §3.11): measured schedule entries
        # + stage costs from build(tune=...), persisted as tune_* bundle
        # keys.  None on untuned / legacy sessions — resolution then
        # falls back to the checked-in defaults.  Installing makes the
        # entries the process-active resolution source for every op
        # wrapper this session's searches launch.
        self.tune_table = tune_table
        if tune_table is not None:
            install(tune_table, merge=True)
        # per-stage selectivity probe for the cascade planner; built
        # once per session (lazily when a legacy bundle lacks one)
        self._calibration = calibration
        # method="auto" cascade choices, memoized per k — the choice is
        # a pure function of (calibration, k), so one sweep serves every
        # plan()/search() of the session (tests pin the count)
        self._cascade_cache: dict[int, CascadePlan] = {}
        # single-device copy of the rows, uploaded on first use by a
        # single-device driver (see _db_j); a session with a mesh holds
        # only the sharded copy
        self._db_dev = None
        self._db_lock = threading.Lock()
        self.mesh = None
        self._axis_names: tuple[str, ...] | None = None
        self._sync_every = 4
        self._db_sharded = None
        self._fingerprint: str | None = None  # lazy, see fingerprint

    # ------------------------------------------------------ constructors

    @classmethod
    def build(
        cls,
        data,
        config: SearchConfig | None = None,
        *,
        index: bool | TriangleIndex = False,
        n_refs: int = 8,
        n_clusters: int | None = None,
        strategy: str = "maxmin",
        seed: int = 0,
        anytime: bool | dict = False,
        tune: bool | dict = False,
    ) -> "Database":
        """Precompute every database-side artifact for ``data`` (N, n).

        ``index=True`` additionally builds the stage-0 triangle index
        (2R banded-DTW sweeps over the database — the expensive artifact
        the bundle exists to amortize); pass a prebuilt
        :class:`TriangleIndex` to attach one instead (it is validated
        against the data and config).

        ``anytime=True`` builds the anytime subsequence tier
        (DESIGN.md §3.10) over the whole-row length; pass a dict to
        customize, e.g. ``anytime=dict(lengths=(64, n), hop=8,
        n_coarse=32, leaf_size=32)`` — see
        :func:`repro.anytime.build_anytime_index` for every knob.  The
        tier enables ``search(..., mode="anytime", budget=...)`` and
        exact search at the built subsequence lengths.

        ``tune=True`` runs the deterministic kernel autotune sweep
        (DESIGN.md §3.11) at this session's (block, n) shape: every
        kernel family's schedule space is timed, the fastest
        bit-identical configs become the session's
        :class:`~repro.kernels.tuning.TuneTable` (persisted as
        ``tune_*`` bundle keys, installed process-wide), and measured
        per-stage costs replace the planner's analytic table.  Pass a
        dict to customize the sweep, e.g. ``tune=dict(iters=1,
        families=("lb_fused", "pipeline"))`` — see
        :func:`repro.kernels.tuning.autotune_session`.  ``tune=False``
        (default) keeps the checked-in per-backend defaults: builds
        stay fast and cold schedules stay sensible.
        """
        config = config if config is not None else SearchConfig()
        _require_x64_for(config)
        raw = np.asarray(data, dtype=config.precision)
        if raw.ndim == 3:
            d = int(raw.shape[2])
            if raw.shape[2] == 1:
                raw = raw[:, :, 0]  # d = 1: the univariate tier verbatim
        elif raw.ndim == 2:
            d = 1
        else:
            raise ValueError(
                f"data must be (N, n) equal-length series or (N, n, d) "
                f"multivariate series, got shape {raw.shape}"
            )
        if config.channels > 0 and config.channels != d:
            raise ValueError(
                f"config.channels={config.channels} but data has {d} "
                f"channel(s) (shape {raw.shape}); pass matching data or "
                f"channels=0 to infer"
            )
        n_db, n = raw.shape[0], raw.shape[1]
        if n < 2:
            raise ValueError(f"series length n={n} must be >= 2")
        w = config.resolve_w(n)
        config.validate_k(config.k, n_db)

        # channel-major flatten: (N, n, d) -> (N, d*n), d contiguous
        # per-channel segments per row (DESIGN.md §3.12); d = 1 is the
        # identity, so the univariate program is byte-identical
        flat = flatten_channels(raw) if raw.ndim == 3 else raw
        if config.znorm:
            # per (row, channel): each channel segment is its own series
            rows = _znorm_rows(
                flat.reshape(n_db * d, n), dtype=config.precision
            ).reshape(n_db, d * n)
        else:
            rows = flat
        raw64 = np.asarray(flat, np.float64)
        row_sums = raw64.sum(axis=1)
        row_sumsq = (raw64 * raw64).sum(axis=1)
        upper, lower = _envelopes_in_chunks(rows, w, d)

        tri: TriangleIndex | None = None
        if index is True:
            tri = build_index(
                rows,
                w=w,
                p=config.p,
                n_refs=n_refs,
                n_clusters=n_clusters,
                strategy=strategy,
                seed=seed,
                d=d,
            )
        elif isinstance(index, TriangleIndex):
            tri = index
            tri.validate(n_db, n, w, config.p, d)
            tri.validate_data(rows)
        elif index is not False:
            raise TypeError(
                f"index must be a bool or a prebuilt TriangleIndex, got "
                f"{type(index).__name__}"
            )
        any_idx = None
        if anytime:
            if d > 1:
                raise ValueError(
                    "anytime subsequence tier is univariate-only for now; "
                    "build with anytime=False for multivariate data"
                )
            from repro.anytime import build_anytime_index

            opts = dict(anytime) if isinstance(anytime, dict) else {}
            any_idx = build_anytime_index(
                raw,
                rows,
                p=config.p,
                znorm=config.znorm,
                resolved_w=w,
                w_config=config.w,
                precision=config.precision,
                seed=opts.pop("seed", seed),
                **opts,
            )
        table = None
        if tune:
            opts = dict(tune) if isinstance(tune, dict) else {}
            table = autotune_session(
                n=n,
                b=opts.pop("b", min(config.block, n_db)),
                w=w,
                p=config.p,
                seed=opts.pop("seed", seed),
                **opts,
            )
        cal = calibrate(rows, w, config.p, d=d)
        return cls(
            raw=raw,
            data=rows,
            config=config,
            w=w,
            upper=upper,
            lower=lower,
            row_sums=row_sums,
            row_sumsq=row_sumsq,
            index=tri,
            calibration=cal,
            anytime=any_idx,
            tune_table=table,
            d=d,
        )

    # ------------------------------------------------------- persistence

    def save(self, path: str) -> str:
        """Persist the whole session — data, envelopes, powered norms,
        stage-0 index, config — to one ``.npz`` bundle."""
        path = npz_path(path)
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        arrays: dict[str, np.ndarray] = {
            "bundle_format_version": np.int64(BUNDLE_FORMAT_VERSION),
            "config_json": np.str_(self.config.to_json()),
            "resolved_w": np.int64(self.w),
            "data": self.raw,
            "upper": self.upper,
            "lower": self.lower,
            "row_sums": self.row_sums,
            "row_sumsq": self.row_sumsq,
        }
        if self.d > 1:
            # optional like cal_*: absent means univariate, so every
            # pre-mv bundle loads unchanged (format version stays 1)
            arrays["channels"] = np.int64(self.d)
        if self.index is not None:
            arrays.update(
                {f"idx_{k}": v for k, v in index_arrays(self.index).items()}
            )
        if self._calibration is not None:
            # optional keys: absent in pre-planner bundles, recomputed
            # lazily on first use — the format version stays the same
            arrays.update(
                {
                    f"cal_{k}": v
                    for k, v in self._calibration.to_arrays().items()
                }
            )
        if self.anytime is not None:
            from repro.anytime import anytime_arrays

            arrays.update(
                {f"any_{k}": v for k, v in anytime_arrays(self.anytime).items()}
            )
        if self.tune_table is not None:
            # optional like cal_*: absent in untuned / legacy bundles,
            # where resolution falls back to the checked-in defaults
            arrays.update(
                {f"tune_{k}": v for k, v in self.tune_table.to_arrays().items()}
            )
        np.savez_compressed(path, **arrays)
        return path

    @classmethod
    def load(cls, path: str) -> "Database":
        """Rebuild a session from a :meth:`save` bundle.

        Saved artifacts (envelopes, norms, index) are loaded, not
        recomputed; only the derived in-memory forms (z-normalized rows,
        the device upload) are re-materialized.
        """
        path = npz_path(path)
        with np.load(path) as z:
            version = int(z["bundle_format_version"])
            if version != BUNDLE_FORMAT_VERSION:
                raise ValueError(
                    f"database bundle format v{version} unsupported "
                    f"(expected v{BUNDLE_FORMAT_VERSION})"
                )
            config = SearchConfig.from_json(str(z["config_json"]))
            _require_x64_for(config)
            raw = np.asarray(z["data"], dtype=config.precision)
            d = int(z["channels"]) if "channels" in z else 1
            flat = flatten_channels(raw) if raw.ndim == 3 else raw
            if config.znorm:
                n_db, total = flat.shape
                rows = _znorm_rows(
                    flat.reshape(n_db * d, total // d),
                    dtype=config.precision,
                ).reshape(n_db, total)
            else:
                rows = flat
            tri = None
            if "idx_meta" in z:
                tri = index_from_arrays(
                    {
                        k[len("idx_"):]: z[k]
                        for k in z.files
                        if k.startswith("idx_")
                    }
                )
            cal = None
            if "cal_stage_names" in z:
                cal = Calibration.from_arrays(
                    {
                        k[len("cal_"):]: z[k]
                        for k in z.files
                        if k.startswith("cal_")
                    }
                )
            any_idx = None
            if "any_meta" in z:
                from repro.anytime import anytime_from_arrays

                any_idx = anytime_from_arrays(
                    {
                        k[len("any_"):]: z[k]
                        for k in z.files
                        if k.startswith("any_")
                    }
                )
            table = None
            if "tune_json" in z:
                table = TuneTable.from_arrays(
                    {
                        k[len("tune_"):]: z[k]
                        for k in z.files
                        if k.startswith("tune_")
                    }
                )
            return cls(
                raw=raw,
                data=rows,
                config=config,
                w=int(z["resolved_w"]),
                upper=z["upper"],
                lower=z["lower"],
                row_sums=z["row_sums"],
                row_sumsq=z["row_sumsq"],
                index=tri,
                calibration=cal,
                anytime=any_idx,
                tune_table=table,
                d=d,
            )

    # -------------------------------------------------------- properties

    @property
    def n_rows(self) -> int:
        return int(self.data.shape[0])

    @property
    def length(self) -> int:
        """Per-channel series length n (the flattened rows are d*n)."""
        return int(self.data.shape[1]) // self.d

    @property
    def channels(self) -> int:
        """Channel count d; 1 for univariate sessions."""
        return self.d

    @property
    def p(self):
        return self.config.p

    @property
    def envelopes(self) -> tuple[np.ndarray, np.ndarray]:
        """(upper, lower) warping envelopes of the database rows, band
        ``self.w`` — computed once at build, persisted in the bundle."""
        return self.upper, self.lower

    @property
    def fingerprint(self) -> str:
        """Stable identity of this session's answer space: sha256 over
        the config's canonical JSON, the resolved band and the raw data
        bytes.  Two sessions share a fingerprint iff every search
        answer they could give is identical, so serving caches
        (``repro.serve``) key on it — a stale config or different data
        can never alias an entry.  Computed once, on first use."""
        if self._fingerprint is None:
            import hashlib

            h = hashlib.sha256()
            h.update(self.config.stable_hash().encode())
            h.update(f"|w={self.w}|{self.raw.shape}|{self.raw.dtype}|".encode())
            h.update(np.ascontiguousarray(self.raw).tobytes())
            self._fingerprint = h.hexdigest()
        return self._fingerprint

    def row_mean_std(self, eps: float = STD_EPS) -> tuple[np.ndarray, np.ndarray]:
        """Per-row mean and (eps-floored) std of the *raw* rows, derived
        O(1) from the cached powered norms — the scale statistics a
        caller needs to normalize external data against this database
        without re-sweeping it.  Multivariate rows pool all d*n scalars
        (per-channel scale lives in the znormed artifacts, not here)."""
        n = self.length * self.d
        mean = self.row_sums / n
        var = np.maximum(self.row_sumsq / n - mean * mean, 0.0)
        return mean, np.maximum(np.sqrt(var), eps)

    def __len__(self) -> int:
        return self.n_rows

    def __repr__(self) -> str:
        shape = f"{self.n_rows} x {self.length}" + (
            f" x {self.d}ch" if self.d > 1 else ""
        )
        return (
            f"Database({shape}, w={self.w}, "
            f"p={self.config.p}, method={self.config.method!r}, "
            f"index={'R=%d' % self.index.n_refs if self.index else 'none'}, "
            f"anytime={list(self.anytime.lengths) if self.anytime else 'none'}, "
            f"mesh={'attached' if self.mesh is not None else 'none'})"
        )

    @property
    def _db_j(self):
        """The rows on the default device, uploaded once, on first use
        by a single-device driver.  ``use_mesh`` releases this copy."""
        with self._db_lock:
            if self._db_dev is None:
                self._db_dev = jnp.asarray(self.data)
            return self._db_dev

    # ---------------------------------------------------------- sharding

    def use_mesh(self, mesh, axis_names=None, sync_every: int = 4) -> "Database":
        """Attach a device mesh: the planner then routes queries through
        the sharded driver.  The database is padded and placed onto the
        mesh here, once — per-call ``device_put`` becomes a no-op.  Each
        device receives only its own shard, and a single-device copy of
        the rows is released (a later explicit single-device ``driver=``
        uploads it again)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.distributed import pad_database

        self.mesh = mesh
        self._axis_names = tuple(
            axis_names if axis_names is not None else mesh.axis_names
        )
        self._sync_every = int(sync_every)
        dbp, _ = pad_database(
            self.data, mesh, self._axis_names, block=self.config.block
        )
        self._db_sharded = jax.device_put(
            dbp, NamedSharding(mesh, P(self._axis_names))
        )
        with self._db_lock:
            self._db_dev = None
        return self

    # ----------------------------------------------------------- queries

    def prepare_queries(self, queries, length: int | None = None) -> np.ndarray:
        """The exact query array the drivers consume: precision-cast and
        (when the session z-norms) z-normalized, shape/length validated.
        Public because the serving engine digests this canonical form —
        under z-norm, scaled/shifted copies of one query prepare to
        identical bytes, which is what makes answer-cache hits on
        near-duplicate traffic exact rather than approximate.
        ``length`` overrides the expected query length for sessions with
        an anytime subsequence tier (default: the whole-row length).

        On a multivariate session (``channels > 1``) queries are one
        (n, d) series or a (Q, n, d) batch; a trailing axis of size 1
        is likewise accepted on univariate sessions.  The returned
        array is channel-major flattened, matching the stored rows."""
        qs = np.asarray(queries, dtype=self.config.precision)
        if qs.ndim == 3 and qs.shape[-1] == 1 and self.d == 1:
            qs = qs[:, :, 0]
        if self.d > 1:
            if qs.ndim == 2 and qs.shape[1] == self.d * self.length:
                # already channel-major flattened (Q, d*n) rows — the
                # serving engine resubmits its prepared queries this
                # way; skip the layout transform, normalization below
                # still applies (idempotent on prepared input)
                if self.config.znorm:
                    nq = qs.shape[0]
                    qs = _znorm_rows(
                        qs.reshape(nq * self.d, self.length),
                        dtype=self.config.precision,
                    ).reshape(nq, self.d * self.length)
                return qs
            single = qs.ndim == 2
            if single:
                qs = qs[None]
            if qs.ndim != 3 or qs.shape[-1] != self.d:
                raise ValueError(
                    f"queries must be one (n, {self.d}) series or a "
                    f"(Q, n, {self.d}) batch on this {self.d}-channel "
                    f"session, got shape "
                    f"{np.asarray(queries).shape}"
                )
            if qs.shape[1] != self.length:
                raise ValueError(
                    f"query length {qs.shape[1]} != expected series "
                    f"length {self.length}: the paper's DTW bounds "
                    f"assume equal lengths"
                )
            qs = np.asarray(flatten_channels(qs))
            if self.config.znorm:
                nq, total = qs.shape
                qs = _znorm_rows(
                    qs.reshape(nq * self.d, self.length),
                    dtype=self.config.precision,
                ).reshape(nq, total)
            return qs[0] if single else qs
        if qs.ndim not in (1, 2):
            raise ValueError(
                f"queries must be one (n,) series or a (Q, n) batch, got "
                f"shape {qs.shape}"
            )
        expected = self.length if length is None else int(length)
        if qs.shape[-1] != expected:
            tiers = (
                f" (anytime tier lengths: {list(self.anytime.lengths)})"
                if self.anytime is not None
                else ""
            )
            raise ValueError(
                f"query length {qs.shape[-1]} != expected series length "
                f"{expected}: the paper's DTW bounds assume equal "
                f"lengths{tiers}"
            )
        if self.config.znorm:
            single = qs.ndim == 1
            qs = _znorm_rows(
                qs[None] if single else qs, dtype=self.config.precision
            )
            if single:
                qs = qs[0]
        return qs

    def _config_for(self, method: str | None) -> SearchConfig:
        """Per-call method override: the stage pipeline never affects
        results or the cached artifacts (those depend only on w, p,
        precision, znorm), so it may vary per call without a rebuild."""
        if method is None:
            return self.config
        return dataclasses.replace(self.config, method=method)

    @property
    def calibration(self) -> Calibration:
        """The per-stage selectivity probe the cascade planner consumes
        — built at :meth:`build`, persisted in the bundle; a legacy
        bundle without one gets it measured here, once."""
        if self._calibration is None:
            self._calibration = calibrate(
                self.data, self.w, self.config.p, d=self.d
            )
        return self._calibration

    def _resolve_method(
        self, cfg: SearchConfig, k: int | None = None
    ) -> tuple[SearchConfig, CascadePlan | None]:
        """``method="auto"`` -> the calibration-chosen stage order; any
        concrete method passes through untouched.  The choice affects
        cost only — every pipeline bit-matches (tier-1 exactness)."""
        if cfg.method != "auto":
            return cfg, None
        kk = cfg.k if k is None else int(k)
        cascade = self._cascade_cache.get(kk)
        if cascade is None:
            # a tuned session plans with its measured stage costs; an
            # untuned one with the analytic table (explain() shows which)
            costs = self.tune_table.stage_costs if self.tune_table else None
            cascade = choose_cascade(self.calibration, k=kk, unit_costs=costs)
            self._cascade_cache[kk] = cascade
        return dataclasses.replace(cfg, method=cascade.method), cascade

    def _anytime_info(self, qlen: int | None = None) -> dict | None:
        """Tier summary for the planner (None when no tier is built)."""
        if self.anytime is None:
            return None
        return {
            "lengths": list(self.anytime.lengths),
            "windows": self.anytime.n_windows,
            "clusters": self.anytime.n_clusters,
            "subsequence": qlen is not None and qlen != self.length,
        }

    @obs.spanned("session.plan")
    def plan(
        self,
        queries=None,
        *,
        driver: str | None = None,
        method: str | None = None,
        k: int | None = None,
        mode: str = "exact",
        budget: int | None = None,
        length: int | None = None,
    ) -> Plan:
        """The routing decision ``search`` would take for ``queries``
        (shape only — nothing but a possible first-use calibration of a
        legacy bundle is computed).  ``Plan.explain()`` renders the
        chosen driver, stage order and reasons; under ``method="auto"``
        it additionally shows the calibrated cascade cost model, and
        under ``mode="anytime"`` the tier route and budget."""
        qlen = length
        if queries is None:
            n_queries = 1
        elif isinstance(queries, (int, np.integer)):
            n_queries = int(queries)
        else:
            arr = np.asarray(queries)
            if self.d > 1:
                # mv shapes: (d*n,) flattened or (n, d) is a single
                # query; (Q, n, d) and flattened (Q, d*n) are batches
                if arr.ndim == 1 or (
                    arr.ndim == 2 and arr.shape[-1] == self.d
                ):
                    n_queries = 1
                else:
                    n_queries = int(arr.shape[0])
            else:
                n_queries = 1 if arr.ndim == 1 else int(arr.shape[0])
                if arr.ndim in (1, 2) and qlen is None:
                    qlen = int(arr.shape[-1])
        cfg, cascade = self._resolve_method(self._config_for(method), k)
        return plan_search(
            cfg,
            self.n_rows,
            n_queries,
            has_index=self.index is not None,
            has_mesh=self.mesh is not None,
            driver=driver,
            cascade=cascade,
            mode=mode,
            budget=budget,
            anytime_info=self._anytime_info(qlen),
            channels=self.d,
        )

    @obs.spanned("session.query")
    def search(
        self,
        queries,
        *,
        k: int | None = None,
        driver: str | None = None,
        method: str | None = None,
        mode: str = "exact",
        budget: int | None = None,
    ):
        """Nearest-neighbour search through the planned pipeline.

        ``queries`` is one (n,) series -> ``SearchResult`` or a (Q, n)
        batch -> ``BatchSearchResult`` (one query-major sweep).  Results
        are bit-identical to the corresponding legacy entry point — the
        facade only amortizes the database-side work.  ``k``, ``driver``
        and ``method`` may be overridden per call (none of them touch
        the cached artifacts); everything else is fixed by the config.

        On a session built with ``anytime=...``, two more routes open
        (both return :class:`repro.anytime.AnytimeResult` /
        ``AnytimeBatchResult`` with window provenance):

        * ``mode="anytime"`` — budgeted best-first cluster exploration:
          best-so-far top-k plus a sound per-answer error bound that
          tightens to 0; ``budget`` caps refined windows per query
          (``None`` = unlimited, at which point the answer bit-matches
          ``mode="exact"``).
        * queries shorter than the whole-row length — served exactly
          (or anytime) against the matching subsequence tier.
        """
        if mode not in ("exact", "anytime"):
            raise ValueError(f"mode={mode!r} unknown; use 'exact' or 'anytime'")
        qlen = int(np.asarray(queries).shape[-1])
        if mode == "anytime" or (
            self.anytime is not None and qlen != self.length
        ):
            return self._search_anytime(
                queries, qlen, k=k, driver=driver, method=method,
                mode=mode, budget=budget,
            )
        if budget is not None:
            raise ValueError(
                "budget= only applies to mode='anytime' (exact search "
                "always explores everything)"
            )
        qs = self.prepare_queries(queries)
        k = self.config.validate_k(
            self.config.k if k is None else k, self.n_rows
        )
        plan = self.plan(qs, driver=driver, method=method, k=k)
        cfg = plan.config  # "auto" resolved to the calibrated cascade
        if plan.driver == "scan":
            return nn_search_scan(
                qs, self._db_j, w=self.w, p=cfg.p, k=k,
                block=cfg.block, method=cfg.method, d=self.d,
            )
        if plan.driver == "host":
            return nn_search_host(
                qs, self._db_j, w=self.w, p=cfg.p, k=k,
                block=cfg.block, method=cfg.method, d=self.d,
            )
        if plan.driver == "indexed":
            return nn_search_indexed(
                qs, self._db_j, self.index, k=k,
                block=cfg.block, method=cfg.method,
            )
        # sharded
        from repro.core.distributed import sharded_nn_search

        return sharded_nn_search(
            qs, self._db_sharded, self.mesh,
            axis_names=self._axis_names, w=self.w, p=cfg.p, k=k,
            block=cfg.block, sync_every=self._sync_every,
            method=cfg.method, d=self.d,
        )

    def _search_anytime(
        self,
        queries,
        qlen: int,
        *,
        k: int | None,
        driver: str | None,
        method: str | None,
        mode: str,
        budget: int | None,
    ):
        """Route a query batch through the anytime tier (DESIGN.md §3.10)."""
        from repro.anytime import anytime_search, exact_subsequence_search

        if self.anytime is None:
            raise ValueError(
                "mode='anytime' needs the anytime tier: build the session "
                "with Database.build(..., anytime=True) (or a dict of "
                "tier options)"
            )
        li = self.anytime.tier(qlen)  # raises with built lengths listed
        single = np.asarray(queries).ndim == 1
        qs = np.atleast_2d(self.prepare_queries(queries, length=qlen))
        k = self.config.validate_k(
            self.config.k if k is None else k, li.n_windows
        )
        # the plan call validates the route (driver conflicts, budget on
        # exact mode) and resolves method="auto" exactly like search()
        plan = self.plan(
            qs, driver=driver, method=method, k=k, mode=mode, budget=budget
        )
        if plan.driver == "anytime":
            res = anytime_search(
                qs, self.anytime, k=k, method=plan.config.method,
                budget=plan.budget,
            )
        else:
            res = exact_subsequence_search(
                qs, self.anytime, k=k, method=plan.config.method,
                block=plan.config.block,
            )
        return res[0] if single else res

    def topk(
        self, queries, k: int, *, driver: str | None = None
    ) -> SearchResult | BatchSearchResult:
        """``search`` with an explicit neighbour count."""
        return self.search(queries, k=k, driver=driver)

    def classify(
        self, labels, queries, *, driver: str = "scan"
    ) -> int | np.ndarray:
        """1-NN classification against per-row ``labels`` (paper §7).

        Defaults to the scan driver — the bit-identical twin of the
        legacy ``repro.core.classify.nn_classify`` loop; pass
        ``driver="indexed"`` on an indexed session to classify through
        stage 0 (same predictions, exactness is driver-independent).
        """
        labels = np.asarray(labels)
        if labels.shape != (self.n_rows,):
            raise ValueError(
                f"labels must be one label per database row "
                f"({self.n_rows},), got shape {labels.shape}"
            )
        res = self.search(queries, k=1, driver=driver)
        if isinstance(res, SearchResult):
            return int(labels[res.index])
        return np.asarray(labels[res.indices[:, 0]])

    # ---------------------------------------------------------- streaming

    def stream(
        self,
        templates=None,
        *,
        threshold,
        hop: int = 1,
        prefilter: bool = True,
        exclusion: int | None = None,
        capacity: int | None = None,
        eps: float = STD_EPS,
    ):
        """A :class:`repro.stream.StreamMatcher` under this session's
        config (w, p, block, method, znorm).

        With ``templates=None`` the database rows are the template bank
        and the build-time envelopes are reused — constructing matchers
        per signal stops re-deriving them.  Explicit ``templates`` get
        their envelopes computed on construction, exactly like the
        legacy constructor.
        """
        from repro.stream.matcher import StreamMatcher

        cfg, _ = self._resolve_method(self.config)
        envelopes = None
        if templates is None:
            templates = self.raw
            # cached envelopes were computed on the (znormed) float32
            # rows with the default std floor; reuse them only when the
            # scanner would recompute exactly that
            if self.config.precision == "float32" and (
                not self.config.znorm or eps == STD_EPS
            ):
                envelopes = (self.upper, self.lower)
        return StreamMatcher(
            templates,
            self.w,
            threshold,
            p=self.config.p,
            hop=hop,
            znorm=self.config.znorm,
            block=self.config.block,
            method=cfg.method,
            prefilter=prefilter,
            exclusion=exclusion,
            capacity=capacity,
            eps=eps,
            envelopes=envelopes,
            d=self.d,
        )
