"""The one traffic generator: it reads a mix's parameters from its data
file (``bench/traffic/<mix>.json``) and a configuration's sizes, and
makes every input of a run from ``--seed``.

The generators are copied from the program (``repro.data.synthetic``
``random_walks`` and ``planted_stream``, ``repro.launch.serve``
``mixed_workload``) so that the yardstick does not move when the
program does.  Differences from the originals are noted where they are.
"""

from __future__ import annotations

import numpy as np

#: sub-streams of one seed, so that adding one input never shifts another
DATA, TRAFFIC, WARMUP, SAMPLE = 0, 1, 2, 3


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one kind of input of one run.  ``seed`` is any
    whole number; negative seeds get their own streams."""
    seed = int(seed)
    return np.random.default_rng(
        np.random.SeedSequence([abs(seed), int(seed < 0), int(stream)])
    )


def random_walks(rng: np.random.Generator, count: int, length: int) -> np.ndarray:
    """x_i = x_{i-1} + N(0,1), x_1 = 0 (the source paper's section 12.1)."""
    steps = rng.standard_normal((count, length)).astype(np.float32)
    steps[:, 0] = 0.0
    return np.cumsum(steps, axis=1)


def query_mix(
    rng: np.random.Generator,
    rows: np.ndarray,
    count: int,
    *,
    repeat_frac: float,
    near_frac: float,
    repeat_pool: int,
    near_sigma: float,
) -> np.ndarray:
    """``repeat_frac`` exact repeats drawn from a pool of ``repeat_pool``
    near-duplicates (answer-cache and coalescing targets),
    ``near_frac`` near-duplicates of database rows at ``near_sigma``,
    the rest cold random walks.  With both fractions 0 every query is a
    fresh walk.

    The order is shuffled but stratified: the j-th of a kind's m queries
    lies at a random point of the j-th m-th of the order, so every
    prefix holds each kind in its share to within two queries.  A window
    answers a prefix, and with a plain shuffle the seed set how many of
    its queries were repeats the answer cache takes (141 to 169 of the
    first 520 at five seeds), and with them the window's work."""
    n_rows, length = rows.shape
    n_rep = int(count * repeat_frac)
    n_near = int(count * near_frac)
    n_cold = count - n_rep - n_near
    parts = []
    if n_rep:
        pool = rows[rng.integers(0, n_rows, repeat_pool)] + rng.normal(
            scale=near_sigma, size=(repeat_pool, length)
        ).astype(np.float32)
        parts.append(pool[rng.integers(0, repeat_pool, n_rep)])
    if n_near:
        parts.append(
            rows[rng.integers(0, n_rows, n_near)]
            + rng.normal(scale=near_sigma, size=(n_near, length)).astype(
                np.float32
            )
        )
    if n_cold:
        parts.append(random_walks(rng, n_cold, length))
    work = np.concatenate(parts, axis=0).astype(np.float32)
    slots = np.concatenate(
        [(np.arange(len(p)) + rng.random(len(p))) / len(p) for p in parts]
    )
    return work[np.argsort(slots, kind="stable")]


def planted_walk_stream(
    rng: np.random.Generator,
    length: int,
    templates: np.ndarray,
    *,
    every: int,
    amp_range: tuple[float, float],
    noise: float,
) -> tuple[np.ndarray, list[tuple[int, int]]]:
    """A random-walk stream with one noisy template occurrence planted in
    every ``every`` samples.

    Unlike ``planted_stream`` (which adds templates to white noise) the
    background is a random walk, as in the UCR Suite's experiments, and
    an occurrence replaces the walk's increments over its span, so the
    stream stays continuous: the ``n`` samples from the occurrence's
    start are ``start value + amp * (t - t[0]) + noise``, where the noise
    is ``noise`` times the template's standard deviation.  Returns
    ``(stream, [(template id, start), ...])``.
    """
    templates = np.asarray(templates, np.float64)
    nq, n = templates.shape
    if every < 2 * n:
        raise ValueError(f"plants every {every} samples overlap at length {n}")
    inc = rng.standard_normal(length)
    inc[0] = 0.0
    plants = []
    for slot in range(length // every):
        pos = slot * every + int(rng.integers(0, every - n))
        tid = int(rng.integers(0, nq))
        amp = float(rng.uniform(*amp_range))
        t = templates[tid]
        shape = amp * t + noise * float(t.std()) * rng.standard_normal(n)
        inc[pos + 1 : pos + n] = np.diff(shape)
        plants.append((tid, pos))
    return np.cumsum(inc).astype(np.float32), plants
