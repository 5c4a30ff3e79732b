"""Windowed subsequence matching over the shared cascade (DESIGN.md §3.5).

The database search answers "which series is nearest to q"; the stream
workload asks "*where* in an unbounded signal does any template match".
Both are the same cascade — this module materializes hop-strided window
blocks from a ``StreamState`` and drives them through the exact stage
pipeline the top-k drivers use (``repro.core.pipeline.run_block_stages``,
DESIGN.md §3.6): windows are the candidate lanes, templates the query
batch, and the per-query pruning bound is a fixed powered threshold
instead of a tightening k-th best.

Stages per block (windows as lanes, templates as query rows):

  S0  envelope prefilter — slices of the *stream* envelope (maintained
      online in O(1)/sample by ``StreamState``) bound LB_Keogh(template,
      window) from below the other way around: the stream envelope over a
      window's positions contains the window's own envelope, so
      ``||q - clip(q, L_str, U_str)||_p <= LB_Keogh(q, c) <= DTW(q, c)``.
      Costs O(n) numpy per window, prunes before any device dispatch and
      before z-normalized windows are even materialized (the z-transform
      is affine per window, so envelope slices transform in O(n) too).
  S1  LB_Keogh          (batched, one dispatch per block)
  S2  LB_Improved pass 2 (survivor-compacted lane chunks)
  S3  banded DTW        (survivor-compacted, early-abandoning at the
                         powered threshold)

A window matches template ``t`` when its powered DTW distance is
``<= threshold[t]^p``; pruning uses ``nextafter(threshold^p)`` so the
strict ``lb < bound`` compare of the shared staging keeps boundary
windows (LB == threshold) alive — the match set is exactly the naive
per-window scan's.

Trivial-match exclusion: overlapping detections of the same template are
collapsed to the best one (``greedy_suppress``: ascending-distance greedy,
a hit survives unless a better *surviving* hit of the same template lies
within ``± exclusion`` samples).  ``suppress_stream`` is the streaming
form: it additionally labels each decision *stable* once no unevaluated
window and no unstable better hit can change it, so ``StreamMatcher``
emits exactly the offline suppression's output, incrementally.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import defaultdict
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.cascade import Method
from repro.core.dtw import PNorm
from repro.core.pipeline import lb_stage_names, run_block_stages
from repro.mv.envelope import envelope_batch_mv
from repro.mv.layout import flatten_channels
from repro.stream.state import STD_EPS, StreamState


class Match(NamedTuple):
    """One detection: template id, window start position, rooted distance."""

    tid: int
    start: int
    dist: float


def num_windows(length: int, n: int, hop: int) -> int:
    """Windows of length ``n`` at starts 0, hop, 2*hop, ... fully inside
    a stream of ``length`` samples."""
    if length < n:
        return 0
    return (length - n) // hop + 1


def znorm_series(x: np.ndarray, eps: float = STD_EPS) -> np.ndarray:
    """Global z-normalization (templates), std floored at ``eps``."""
    x64 = np.asarray(x, np.float64)
    mean = x64.mean()
    std = max(float(x64.std()), eps)
    return ((x64 - mean) / std).astype(np.float32)


def znorm_windows(
    wins: np.ndarray, mean: np.ndarray, std: np.ndarray
) -> np.ndarray:
    """Per-window z-normalization with precomputed rolling stats."""
    z = (wins.astype(np.float64) - mean[:, None]) / std[:, None]
    return z.astype(np.float32)


def powered_threshold(threshold: np.ndarray, p: PNorm) -> np.ndarray:
    """Rooted per-template threshold -> float32 powered domain."""
    thr = np.asarray(threshold, np.float64)
    if p == np.inf or p == 1:
        pw = thr
    else:
        pw = thr**p
    return pw.astype(np.float32)


def envelope_prefilter(
    qs: np.ndarray, u_wins: np.ndarray, l_wins: np.ndarray, p: PNorm
) -> np.ndarray:
    """Powered LB_Keogh(template, window-envelope) — (Q, B) from (Q, n)
    templates and (B, n) per-window envelope slices.  Any elementwise
    widening of the true window envelope keeps this a valid DTW lower
    bound, so stream-envelope slices (which cover a superset of each
    window) are admissible."""
    d = np.maximum(qs[:, None, :] - u_wins[None], 0.0) + np.maximum(
        l_wins[None] - qs[:, None, :], 0.0
    )
    if p == np.inf:
        return np.max(d, axis=-1)
    if p == 1:
        return np.sum(d, axis=-1)
    if p == 2:
        return np.sum(d * d, axis=-1)
    return np.sum(d**p, axis=-1)


def finish_np(acc: np.ndarray, p: PNorm) -> np.ndarray:
    """Powered -> rooted distance (numpy twin of core.dtw.finish_cost)."""
    if p == np.inf or p == 1:
        return acc
    if p == 2:
        return np.sqrt(acc)
    return acc ** (1.0 / p)


@functools.partial(jax.jit, static_argnames=("w", "p", "method", "d"))
def _match_block_jit(qs, upper, lower, blk, bound, mask0, w, p, method, d=1):
    """One window block through the shared stage pipeline (fixed
    per-template powered bound; lanes masked off by the prefilter are
    neither evaluated nor counted)."""
    return run_block_stages(
        qs, upper, lower, w, p, method, blk, bound, mask0, d=d
    )


@dataclasses.dataclass
class StreamStats:
    """Per-stage window accounting, one counter lane per template.

    ``env_pruned + stage_pruned.sum(axis=0) + full_dtw == n_windows``
    holds per template (the streaming analogue of ``SearchStats``'
    invariant); ``stage_pruned`` is (S, Q), one row per LB stage of the
    method's pipeline in cascade order, and ``lb1_pruned``/
    ``lb2_pruned`` are back-compat views (first stage / all later
    stages).  ``blocks_*`` count executions of the shared batched
    sweep.  ``env_pruned`` depends on how much of the stream had arrived
    when a block was processed (right-truncated tail envelopes are
    tighter), so it may shift between S0 and S1 across different
    chunkings — the match set never does.
    """

    n_templates: int
    stage_names: tuple[str, ...]  # LB stages of the method, cascade order
    n_windows: np.ndarray  # (Q,) windows evaluated per template
    env_pruned: np.ndarray  # (Q,) killed by the S0 stream-envelope bound
    stage_pruned: np.ndarray  # (S, Q) killed by each LB stage
    full_dtw: np.ndarray  # (Q,) windows that reached the banded DP
    matched: np.ndarray  # (Q,) raw hits below threshold (pre-exclusion)
    blocks_total: int = 0
    blocks_lb2: int = 0
    blocks_dtw: int = 0
    # DP lane economics, batch-level like blocks_* (DESIGN.md §3.6):
    # lanes the compacted DP actually executed vs alive lanes among them
    dp_lane_work: int = 0
    dp_lane_useful: int = 0

    @classmethod
    def zeros(
        cls,
        n_templates: int,
        stage_names: tuple[str, ...] = ("lb_keogh", "lb_improved"),
    ) -> "StreamStats":
        z = lambda: np.zeros(n_templates, np.int64)
        sp = np.zeros((len(stage_names), n_templates), np.int64)
        return cls(n_templates, stage_names, z(), z(), sp, z(), z())

    @property
    def lb1_pruned(self) -> np.ndarray:
        """(Q,) windows killed by the first LB stage (back-compat view)."""
        if len(self.stage_names) == 0:
            return np.zeros(self.n_templates, np.int64)
        return self.stage_pruned[0]

    @property
    def lb2_pruned(self) -> np.ndarray:
        """(Q,) windows killed by any later LB stage (back-compat view)."""
        return self.stage_pruned[1:].sum(axis=0)

    @property
    def pruned_by(self) -> dict[str, np.ndarray]:
        """Per-stage (Q,) kill counts keyed by stage name."""
        return dict(zip(self.stage_names, self.stage_pruned))

    @property
    def pruned_before_dtw(self) -> float:
        """Fraction of (template, window) lanes killed before the DP."""
        total = int(self.n_windows.sum())
        if total == 0:
            return 0.0
        return 1.0 - int(self.full_dtw.sum()) / total

    @property
    def dp_lane_efficiency(self) -> float:
        """useful / work of the DP lanes actually executed (1.0 when the
        DP never ran)."""
        if self.dp_lane_work == 0:
            return 1.0
        return self.dp_lane_useful / self.dp_lane_work


def _read(x) -> np.ndarray:
    """A blocking device-to-host read, timed as ``stream.wait``."""
    with obs.span("stream.wait"):
        return np.asarray(x)


class SubsequenceScanner:
    """Block engine: windows-as-lanes sweep of the template batch.

    Owns the (optionally z-normalized) templates, their envelopes, the
    powered thresholds and the per-stage counters; ``process_block``
    pulls one hop-strided block of windows out of a ``StreamState`` and
    returns its raw sub-threshold hits.  Drivers (``StreamMatcher``
    online, ``windowed_matches`` offline) own window scheduling and
    trivial-match exclusion.
    """

    def __init__(
        self,
        templates: np.ndarray,
        w: int,
        threshold,
        *,
        p: PNorm = 1,
        hop: int = 1,
        znorm: bool = False,
        block: int = 64,
        method: Method = "lb_improved",
        prefilter: bool = True,
        eps: float = STD_EPS,
        envelopes: tuple[np.ndarray, np.ndarray] | None = None,
        d: int = 1,
    ):
        self.d = int(d)
        if self.d < 1:
            raise ValueError(f"d must be >= 1 channels, got {d}")
        templates = np.asarray(templates, np.float32)
        if self.d > 1:
            # multivariate templates: (n, d) single or (Q, n, d) batch,
            # flattened channel-major to the (Q, d*n) row layout every
            # driver shares (DESIGN.md §3.12)
            if templates.ndim == 2:
                templates = templates[None]
            if templates.ndim != 3 or templates.shape[-1] != self.d:
                raise ValueError(
                    f"multivariate templates must be (n, {self.d}) or "
                    f"(Q, n, {self.d}); got shape {templates.shape}"
                )
            self.nq, self.n = templates.shape[0], templates.shape[1]
            templates = np.asarray(flatten_channels(templates))
        else:
            templates = np.atleast_2d(templates)
            self.nq, self.n = templates.shape
        if hop <= 0:
            raise ValueError(f"hop must be positive, got {hop}")
        if block <= 0:
            raise ValueError(f"block must be positive, got {block}")
        self.w = int(min(w, self.n - 1))
        self.p = p
        self.hop = int(hop)
        self.znorm = bool(znorm)
        self.block = int(block)
        self.method: Method = method
        self.prefilter = bool(prefilter)
        self.eps = float(eps)
        if znorm:
            # per (template, channel): each channel segment of the
            # flattened row is its own series (a no-op reshape at d=1)
            seg = templates.reshape(self.nq * self.d, self.n)
            seg = np.stack([znorm_series(t, eps) for t in seg])
            templates = seg.reshape(self.nq, self.d * self.n)
        self.templates = templates
        thr = np.broadcast_to(
            np.asarray(threshold, np.float64), (self.nq,)
        ).astype(np.float64)
        if np.any(thr < 0):
            raise ValueError("thresholds must be >= 0")
        self.threshold = thr  # rooted, per template
        self.thr_pow = powered_threshold(thr, p)  # float32 powered
        # strict `lb < bound` in the shared staging must keep lb == thr
        self.gate = np.nextafter(self.thr_pow, np.float32(np.inf))
        if envelopes is None:
            u, l = envelope_batch_mv(jnp.asarray(templates), self.w, self.d)
        else:
            # prebuilt template envelopes (a repro.api.Database build
            # artifact): must match the post-znorm templates at band w
            u_np, l_np = (np.asarray(e, np.float32) for e in envelopes)
            if u_np.shape != templates.shape or l_np.shape != templates.shape:
                raise ValueError(
                    f"prebuilt envelopes shaped {u_np.shape}/{l_np.shape} do "
                    f"not match the template bank {templates.shape}"
                )
            # a valid envelope contains its series; too-tight envelopes
            # (wrong band, or built pre-znorm for a znorm scanner) would
            # silently prune true matches — refuse them here
            if not ((u_np >= templates).all() and (l_np <= templates).all()):
                raise ValueError(
                    "prebuilt envelopes do not contain the (post-znorm) "
                    "templates — they were built at a different band or "
                    "normalization and would make the LB cascade unsound"
                )
            u, l = jnp.asarray(u_np), jnp.asarray(l_np)
        self._qs_j = jnp.asarray(templates)
        self._u_j, self._l_j = u, l
        self._gate_j = jnp.asarray(self.gate)
        self.stats = StreamStats.zeros(self.nq, lb_stage_names(method))

    @property
    def span(self) -> int:
        """Samples covered by one full block of windows."""
        return (self.block - 1) * self.hop + self.n

    def process_block(
        self, state, start0: int, n_valid: int
    ) -> list[Match]:
        """Evaluate windows starting at ``start0 + hop*i`` for
        ``i < n_valid`` (the rest of the block is masked padding).
        Returns raw sub-threshold hits, exclusion not yet applied.

        ``state`` is one :class:`StreamState` for univariate scanners
        and a sequence of ``d`` channel states (pushed in lockstep) for
        multivariate ones.
        """
        if n_valid <= 0:
            return []
        with obs.span("stream.block", windows=int(n_valid)):
            return self._block(state, start0, n_valid)

    def _block(self, state, start0: int, n_valid: int) -> list[Match]:
        """``process_block``'s body.  Its leaves tile the block:
        ``stream.windows`` and ``stream.prefilter`` (in the lane builder),
        ``stream.match`` (uploads and launch), a ``stream.wait`` per
        device read, and ``stream.tally`` (stats and hits)."""
        n, hop, block = self.n, self.hop, self.block
        starts = start0 + hop * np.arange(block, dtype=np.int64)
        valid = np.arange(block) < n_valid
        avail = starts[n_valid - 1] + n - start0  # samples really present
        if self.d == 1:
            wins, mask0 = self._window_lanes(state, start0, avail, starts, valid)
        else:
            wins, mask0 = self._window_lanes_mv(
                state, start0, avail, starts, valid
            )

        with obs.span("stream.match"):
            res = _match_block_jit(
                self._qs_j,
                self._u_j,
                self._l_j,
                jnp.asarray(wins),
                self._gate_j,
                jnp.asarray(mask0),
                self.w,
                self.p,
                self.method,
                self.d,
            )
        d = _read(res.d)
        masks = [_read(m) for m in res.masks]

        with obs.span("stream.tally"):
            st = self.stats
            st.n_windows += n_valid
            for s in range(len(st.stage_names)):
                st.stage_pruned[s] += (masks[s] & ~masks[s + 1]).sum(axis=1)
            st.full_dtw += masks[-1].sum(axis=1)
            st.blocks_total += 1
            st.blocks_lb2 += int(_read(res.need_lb2))
            st.blocks_dtw += int(_read(res.need_dtw))
            st.dp_lane_work += int(_read(res.dp_lane_work))
            st.dp_lane_useful += int(_read(res.dp_lane_useful))

            hit = d <= self.thr_pow[:, None]
            st.matched += hit.sum(axis=1)
            rooted = finish_np(d.astype(np.float64), self.p)
            out = []
            for qi, bi in zip(*np.nonzero(hit)):
                out.append(Match(int(qi), int(starts[bi]), float(rooted[qi, bi])))
            return out

    def _window_lanes(self, state, start0, avail, starts, valid):
        """Univariate lane builder: (block, n) windows + S0 mask."""
        n, hop, block = self.n, self.hop, self.block
        with obs.span("stream.windows"):
            seg = state.view(start0, avail)
            if avail < self.span:  # tail block: pad so strides stay static
                seg = np.concatenate(
                    [seg, np.zeros(self.span - avail, seg.dtype)]
                )
            wins = np.lib.stride_tricks.sliding_window_view(seg, n)[::hop][
                :block
            ]

            if self.znorm:
                mean, std = state.window_mean_std(
                    np.where(valid, starts, starts[0]), n, self.eps
                )
                wins = znorm_windows(wins, mean, std)
            else:
                wins = np.ascontiguousarray(wins)
                mean = std = None

            mask0 = np.broadcast_to(valid[None, :], (self.nq, block)).copy()
        if self.prefilter:
            with obs.span("stream.prefilter"):
                u_seg, l_seg = state.envelope_view(start0, avail)
                if avail < self.span:
                    pad = self.span - avail
                    u_seg = np.concatenate([u_seg, np.zeros(pad, u_seg.dtype)])
                    l_seg = np.concatenate([l_seg, np.zeros(pad, l_seg.dtype)])
                u_w = np.lib.stride_tricks.sliding_window_view(u_seg, n)[::hop][
                    :block
                ]
                l_w = np.lib.stride_tricks.sliding_window_view(l_seg, n)[::hop][
                    :block
                ]
                if self.znorm:
                    u_w = ((u_w - mean[:, None]) / std[:, None]).astype(
                        np.float32
                    )
                    l_w = ((l_w - mean[:, None]) / std[:, None]).astype(
                        np.float32
                    )
                lb0 = envelope_prefilter(self.templates, u_w, l_w, self.p)
                alive0 = mask0 & (lb0 < self.gate[:, None])
                self.stats.env_pruned += (mask0 & ~alive0).sum(axis=1)
                mask0 = alive0
        return wins, mask0

    def _window_lanes_mv(self, states, start0, avail, starts, valid):
        """Multivariate lane builder: per-channel windows concatenated
        channel-major into (block, d*n) flattened lanes.

        Each channel ``c`` has its own ``StreamState`` (pushed in
        lockstep, so all share one position axis); its windows, rolling
        z-norm stats and stream-envelope slices are extracted exactly
        like the univariate path, then concatenated in channel order —
        the same ``(n, d) -> (d*n,)`` layout the templates were
        flattened to, under which the shared cascade computes the
        dependent-DTW bounds (DESIGN.md §3.12).  The S0 prefilter stays
        sound channel-wise: each channel's stream envelope contains the
        window's own channel envelope, and ``envelope_prefilter`` on the
        flattened rows is the channel-summed (p < inf) / channel-maxed
        (p = inf) LB_Keogh.
        """
        if len(states) != self.d:
            raise ValueError(
                f"multivariate scanner needs {self.d} channel states, "
                f"got {len(states)}"
            )
        n, hop, block = self.n, self.hop, self.block
        sw = np.lib.stride_tricks.sliding_window_view
        valid_starts = np.where(valid, starts, starts[0])
        pad = max(self.span - avail, 0)
        ch_wins, ch_stats = [], []
        for st in states:
            seg = st.view(start0, avail)
            if pad:
                seg = np.concatenate([seg, np.zeros(pad, seg.dtype)])
            w_c = sw(seg, n)[::hop][:block]
            if self.znorm:
                mean, std = st.window_mean_std(valid_starts, n, self.eps)
                w_c = znorm_windows(w_c, mean, std)
                ch_stats.append((mean, std))
            else:
                w_c = np.ascontiguousarray(w_c)
            ch_wins.append(w_c)
        wins = np.concatenate(ch_wins, axis=1)

        mask0 = np.broadcast_to(valid[None, :], (self.nq, block)).copy()
        if self.prefilter:
            u_parts, l_parts = [], []
            for ci, st in enumerate(states):
                u_seg, l_seg = st.envelope_view(start0, avail)
                if pad:
                    u_seg = np.concatenate(
                        [u_seg, np.zeros(pad, u_seg.dtype)]
                    )
                    l_seg = np.concatenate(
                        [l_seg, np.zeros(pad, l_seg.dtype)]
                    )
                u_w = sw(u_seg, n)[::hop][:block]
                l_w = sw(l_seg, n)[::hop][:block]
                if self.znorm:
                    mean, std = ch_stats[ci]
                    u_w = ((u_w - mean[:, None]) / std[:, None]).astype(
                        np.float32
                    )
                    l_w = ((l_w - mean[:, None]) / std[:, None]).astype(
                        np.float32
                    )
                u_parts.append(u_w)
                l_parts.append(l_w)
            u_all = np.concatenate(u_parts, axis=1)
            l_all = np.concatenate(l_parts, axis=1)
            lb0 = envelope_prefilter(self.templates, u_all, l_all, self.p)
            alive0 = mask0 & (lb0 < self.gate[:, None])
            self.stats.env_pruned += (mask0 & ~alive0).sum(axis=1)
            mask0 = alive0
        return wins, mask0


# ------------------------------------------------- trivial-match exclusion


def _order(hits: Iterable[Match]) -> list[Match]:
    return sorted(hits, key=lambda h: (h.dist, h.start, h.tid))


def greedy_suppress(hits: Iterable[Match], exclusion: int) -> list[Match]:
    """Offline trivial-match exclusion: ascending-distance greedy.  A hit
    survives unless a better *surviving* hit of the same template starts
    within ``exclusion`` samples (ties broken by start, then template
    id).  Returned in stream order."""
    kept: list[Match] = []
    kept_by_tid: dict[int, list[int]] = defaultdict(list)
    for h in _order(hits):
        if all(abs(h.start - s) >= exclusion for s in kept_by_tid[h.tid]):
            kept.append(h)
            kept_by_tid[h.tid].append(h.start)
    return sorted(kept, key=lambda h: (h.start, h.tid))


@dataclasses.dataclass
class _Decision:
    hit: Match
    accepted: bool
    stable: bool


def suppress_stream(
    hits: Iterable[Match], frontier: float, exclusion: int
) -> tuple[list[Match], list[Match], list[Match]]:
    """Streaming trivial-match exclusion with stability labelling.

    Runs the same ascending-distance greedy as ``greedy_suppress`` over
    the hits seen so far, then labels a decision *stable* when nothing
    that arrives later can change it: every window start within
    ``exclusion`` of the hit has been evaluated (``frontier`` is the
    next unevaluated start, ``inf`` after a flush) **and** every better
    hit inside its exclusion zone — accepted or not — is itself stable.
    The second condition resolves suppression chains (a better hit that
    might itself be un-suppressed by a still-better future hit would
    flip this one), so emitted decisions provably equal the offline
    greedy over the complete hit set.

    Returns ``(stable_accepted, stable_suppressed, pending)``.
    """
    decisions: list[_Decision] = []
    by_tid: dict[int, list[_Decision]] = defaultdict(list)
    for h in _order(hits):
        zone = [
            e
            for e in by_tid[h.tid]
            if abs(e.hit.start - h.start) < exclusion
        ]
        accepted = not any(e.accepted for e in zone)
        stable = frontier >= h.start + exclusion and all(
            e.stable for e in zone
        )
        e = _Decision(h, accepted, stable)
        decisions.append(e)
        by_tid[h.tid].append(e)
    acc = [e.hit for e in decisions if e.stable and e.accepted]
    rej = [e.hit for e in decisions if e.stable and not e.accepted]
    pend = [e.hit for e in decisions if not e.stable]
    key = lambda h: (h.start, h.tid)
    return sorted(acc, key=key), sorted(rej, key=key), sorted(pend, key=key)
