"""Plain k-nearest-neighbour search under banded DTW, and the comparison
that decides ``correct`` for the k-NN cells.

The reference computes the distance of a query to every database row
(no bound, no pruning, no batching across queries) and sorts them.
"""

from __future__ import annotations

import numpy as np

from bench.reference.dtw import cross_distances


def all_distances(
    queries: np.ndarray,
    rows: np.ndarray,
    w: int,
    p: int,
    *,
    dtype: str = "float32",
    pairs_per_call: int = 1 << 15,
) -> np.ndarray:
    """(Q, R) DTW distance of every query to every row."""
    return cross_distances(
        queries, rows, w, p, dtype=dtype, pairs_per_call=pairs_per_call
    )


def topk(dist: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """The k smallest distances of each row of ``dist`` and their
    column indices, ascending; ties go to the lower index."""
    idx = np.argsort(dist, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(dist, idx, axis=1), idx


def compare(
    got_dist: np.ndarray, got_idx: np.ndarray, dist: np.ndarray
) -> dict[str, float]:
    """The numbers compared for a batch of answers.

    * ``dist_gap``: the widest relative gap between an answer's r-th
      distance and the reference's r-th smallest distance.
    * ``index_gap``: the widest relative gap between an answer's r-th
      distance and the reference's distance to the row the answer names.
      A row that does not exist reads as infinite.

    Both are ties-proof: two rows at the same distance may be named in
    either order.
    """
    got_dist = np.asarray(got_dist, np.float64)
    got_idx = np.asarray(got_idx, np.int64)
    k = got_dist.shape[1]
    want, _ = topk(dist, k)
    scale = np.maximum(np.abs(want), np.finfo(np.float32).tiny)
    dist_gap = float(np.max(np.abs(got_dist - want) / scale))
    n_rows = dist.shape[1]
    inside = (got_idx >= 0) & (got_idx < n_rows)
    named = np.take_along_axis(dist, np.clip(got_idx, 0, n_rows - 1), axis=1)
    named = np.where(inside, named, np.inf)
    index_gap = float(
        np.max(
            np.abs(named - got_dist)
            / np.maximum(np.abs(got_dist), np.finfo(np.float32).tiny)
        )
    )
    return {"dist_gap": dist_gap, "index_gap": index_gap}
