"""The plain reference over a collection held as file-order shares.

Share ``s`` lives on one device and holds the file range that follows the
earlier shares. :func:`chipbench.reference.knn` runs over each share on
its own device (in a thread each, so the devices work at once), local
positions become file positions, and the lists merge on the host by
(distance, position). Nothing of the program under test is used: not its
index, not its router's merge.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from chipbench import reference


def _on_device(raw: jax.Array, queries: np.ndarray, k: int, low: bool):
    (device,) = raw.devices()
    with jax.default_device(device):
        return reference.knn(raw, queries, k, low=low)


def merge(dists: list, positions: list, k: int) -> tuple:
    """The k smallest of each row of the concatenated lists, ascending by
    distance, ties by position."""
    d = np.concatenate(dists, axis=1)
    p = np.concatenate(positions, axis=1)
    order = np.lexsort((p, d), axis=1)[:, :k]
    return (np.take_along_axis(d, order, axis=1),
            np.take_along_axis(p, order, axis=1))


def knn(shares: list, queries: np.ndarray, k: int,
        low: bool = False) -> tuple:
    """(Q, k) ascending squared distances and file positions over the
    shares; ``low`` is the bfloat16 control, as in ``reference.knn``."""
    with ThreadPoolExecutor(len(shares)) as pool:
        found = list(pool.map(
            lambda raw: _on_device(raw, queries, k, low), shares))
    starts = np.cumsum([0] + [raw.shape[0] for raw in shares[:-1]])
    return merge([d for d, _ in found],
                 [p + start for (_, p), start in zip(found, starts)], k)
