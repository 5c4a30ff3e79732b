"""Subsequence matching over a stream, served by ``QueryEngine.open_stream``.

Set-up makes the template bank and the whole stream from the seed, sets
each template's threshold at a fixed alarm share of windows spread over
the whole stream, builds the session, and feeds a separate warm-up
stream through a stream session of its own so that every program the
window drives is compiled.  The window feeds the stream in fixed chunks
through ``StreamSession.feed`` as fast as the session takes them.
Afterwards the session is closed, which finalises the matches of the
samples fed, and every match is compared with the plain reference over
the same samples.
"""

from __future__ import annotations

import time

import numpy as np

from bench import generate
from bench.reference import stream as ref
from bench.reference.dtw import cross_distances


def calibrate_thresholds(
    templates: np.ndarray, stream: np.ndarray, w: int, p: int, *, windows: int,
    alarm_share: float,
) -> np.ndarray:
    """Per-template threshold: the ``alarm_share`` quantile of the
    template's DTW distances to ``windows`` z-normalised windows whose
    starts are spread evenly over the whole of ``stream``.

    Each template then alarms on about ``alarm_share`` of the stream's
    windows whatever the seed, as a monitoring user sets a threshold by
    the alarm rate they will tolerate, so the seed does not set how many
    windows reach the DP and the exclusion.  (``repro.launch.stream
    .calibrate_thresholds`` takes a fraction of the median distance of
    the stream's first windows instead.)"""
    n = templates.shape[1]
    if stream.size < n + windows - 1:
        raise ValueError(
            f"a stream of {stream.size} samples holds fewer than {windows} "
            f"distinct windows of {n}"
        )
    starts = np.linspace(0, stream.size - n, windows).round().astype(np.int64)
    wins = np.lib.stride_tricks.sliding_window_view(stream, n)[starts]
    dist = cross_distances(ref.znorm_rows(templates), ref.znorm_rows(wins), w, p)
    return np.quantile(dist, alarm_share, axis=1)


class Cell:
    """One stream cell: the session, its templates and the stream."""

    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float, spans):
        from repro.api import Database, SearchConfig
        from repro.serve import QueryEngine

        if traffic["loop"] != "stream":
            raise ValueError(f"stream serves stream traffic, not {traffic['loop']!r}")
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.spans = spans
        n = config["length"]
        rng = generate.rng_for(seed, generate.DATA)
        self.templates = generate.random_walks(rng, config["templates"], n)
        total = int(traffic["max_samples_per_s"] * seconds) + traffic["chunk"]
        self.stream, self.plants = generate.planted_walk_stream(
            generate.rng_for(seed, generate.TRAFFIC),
            total,
            self.templates,
            every=traffic["plant_every"],
            amp_range=tuple(traffic["plant_amp"]),
            noise=traffic["plant_noise"],
        )
        self.threshold = calibrate_thresholds(
            self.templates,
            self.stream,
            config["w"],
            config["p"],
            windows=traffic["calibration_windows"],
            alarm_share=traffic["alarm_share"],
        )
        self.db = Database.build(
            self.templates,
            SearchConfig(
                w=config["w"],
                p=config["p"],
                block=config["block"],
                method=config["method"],
                znorm=config["znorm"],
                precision=config["precision"],
            ),
        )
        if self.db.w != config["w"]:
            raise ValueError(f"band resolved to {self.db.w}, config says {config['w']}")
        self.engine = QueryEngine(self.db)
        self.fed = 0
        self.matches: list = []
        self.counters: dict = {}

    def _open(self):
        return self.engine.open_stream(
            threshold=self.threshold,
            hop=self.config["hop"],
            prefilter=self.config["prefilter"],
            exclusion=self.config["exclusion"],
        )

    # ------------------------------------------------------------ set-up

    def warmup(self) -> None:
        """Feed a stream the window never sees, in the window's chunks,
        long enough to run the cascade to the DP, then close it."""
        chunk = self.traffic["chunk"]
        warm, _ = generate.planted_walk_stream(
            generate.rng_for(self.seed, generate.WARMUP),
            self.traffic["warmup_chunks"] * chunk,
            self.templates,
            every=self.traffic["plant_every"],
            amp_range=tuple(self.traffic["plant_amp"]),
            noise=self.traffic["plant_noise"],
        )
        sess = self._open()
        for lo in range(0, warm.size, chunk):
            sess.feed(warm[lo : lo + chunk])
        sess.close()

    # ------------------------------------------------------------ window

    def run_window(self, seconds: float) -> dict:
        chunk = self.traffic["chunk"]
        stream = self.stream
        sess = self._open()
        self.session = sess
        out = []
        fed = 0
        chunk_s = []
        t0 = time.perf_counter()
        t_stop = t0 + seconds
        with self.spans.span("bench.window"):
            while time.perf_counter() < t_stop:
                if fed + chunk > stream.size:
                    raise RuntimeError(
                        f"the window fed all {stream.size} pre-generated "
                        f"samples; raise max_samples_per_s in the traffic file"
                    )
                with self.spans.span("stream.feed") as s:
                    out.extend(sess.feed(stream[fed : fed + chunk]))
                chunk_s.append(time.perf_counter() - s.t0)
                fed += chunk
        t_end = time.perf_counter()
        st = sess.stats
        windows = int(st.n_windows.sum())
        self.counters = {
            "window_s": t_end - t0,
            "t0": t0,
            "t_end": t_end,
            "samples": fed,
            "windows": windows,
            "full_dtw": int(st.full_dtw.sum()),
            "chunks": fed // chunk,
            "chunk_ms_median": 1e3 * float(np.median(chunk_s)),
            "chunk_ms_max": 1e3 * max(chunk_s),
        }
        # finalise what was fed (not timed): the flush evaluates the last
        # partial block and settles every pending exclusion decision
        out.extend(sess.close())
        self.fed = fed
        self.matches = [(int(m.tid), int(m.start), float(m.dist)) for m in out]
        return {
            "attempted": fed // chunk,
            "failed": 0,
            "values": {"samples_per_s": fed / (t_end - t0)},
        }

    # ------------------------------------------------------------ check

    def free(self) -> None:
        self.engine.close()
        self.engine = None
        self.session = None
        self.db = None

    def reference(self, dtype: str) -> np.ndarray:
        cfg = self.config
        return ref.window_distances(
            self.stream[: self.fed],
            self.templates,
            cfg["w"],
            cfg["p"],
            dtype=dtype,
            windows_per_call=cfg["check"]["windows_per_call"],
        )

    def check(self) -> dict:
        limits = self.config["check"]["limits"]
        dist = self.reference(self.config["precision"])
        nums = ref.compare(
            self.matches,
            dist,
            self.threshold,
            self.config["exclusion"],
            limits["dist_gap"],
        )
        nums["unfed"] = float(self.fed == 0)
        return {k: {"value": nums[k], "limit": limits[k]} for k in limits}

    def control(self, dtype: str) -> dict:
        """The reference in ``dtype`` put in the program's place: its own
        matches over the same samples, compared as the program's are."""
        low = self.reference(dtype)
        got = ref.greedy_exclusion(low, self.threshold, self.config["exclusion"])
        dist = self.reference(self.config["precision"])
        return ref.compare(
            got,
            dist,
            self.threshold,
            self.config["exclusion"],
            self.config["check"]["limits"]["dist_gap"],
        )
