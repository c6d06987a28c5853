"""Work the algorithm needs, and the chip's peaks.

Every count here is a function of a cell's shapes and of the fitted trees'
row counts (their leaf covers) alone: never of the implementation's
buffers, tiles, lane padding or one-hot matmuls.  So a kernel that does
the same algorithm with less waste is measured against the same work, and
no share of a roofline that a correct run reads can pass 100%.

A roofline share is ``max(flops / peak_flops, bytes / peak_bytes) / time``:
the least time the chip could take over the time it took.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Sequence, Tuple

import numpy as np

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# A kind that is not here is an error, not a default.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,             # bf16 matrix units
        "bytes_per_s": 819e9,              # HBM bandwidth
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM, 16 GB HBM per chip",
    },
}

CODE_BYTES = 1          # a bin code is one uint8
F32 = 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scale(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def peaks(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time(work: Work, device_kind: str) -> Tuple[float, str]:
    """Seconds the chip needs at its peaks, and which peak bounds it."""
    p = peaks(device_kind)
    t_flops = work.flops / float(p["flops_per_s"])
    t_bytes = work.bytes / float(p["bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def share(work: Work, seconds: float, device_kind: str
          ) -> Tuple[float, str]:
    """Roofline share in %, and the bound that applied."""
    t, bound = least_time(work, device_kind)
    return 100.0 * t / seconds, bound


def node_rows(leaf_cover: Sequence[float]) -> list:
    """Per level of a heap tree, the row count of each node, from the
    ``2^D`` leaf covers: ``out[l]`` has ``2^l`` entries."""
    level = np.asarray(leaf_cover, np.float64)
    out = [level]
    while level.shape[0] > 1:
        level = level[0::2] + level[1::2]
        out.insert(0, level)
    return out[:-1]                        # internal levels 0 .. D-1


def hist_rows_built(leaf_cover: Sequence[float]) -> Tuple[float, int]:
    """Rows read and node histograms written to build one tree's levels
    with sibling subtraction: every row at the root, then at each deeper
    level only the smaller child of each parent (the sibling is the
    parent's histogram less the built one)."""
    levels = node_rows(leaf_cover)
    rows = float(levels[0][0])
    nodes = 1
    for lvl in levels[1:]:
        pairs = lvl.reshape(-1, 2)
        rows += float(np.minimum(pairs[:, 0], pairs[:, 1]).sum())
        nodes += pairs.shape[0]
    return rows, nodes


def hist_work(leaf_cover: Sequence[float], m: int, k: int,
              n_bins: int) -> Work:
    """One tree's histograms: each built row's ``m`` codes and ``k + 1``
    stats read once, one add per code and channel, and each built node's
    ``m x n_bins x (k + 1)`` float32 histogram written once."""
    rows, nodes = hist_rows_built(leaf_cover)
    c = k + 1
    return Work(flops=rows * m * c,
                bytes=rows * (m * CODE_BYTES + c * F32)
                + nodes * m * n_bins * c * F32)


def round_work(leaf_cover: Sequence[float], *, n: int, n_eval: int, m: int,
               d: int, k: int, depth: int, n_bins: int,
               dense_targets: bool, sketched: bool = True) -> Work:
    """One boosting round of the single-tree sketch, with its eval pass.

    Bytes: the scores ``F`` (n x d) read twice (gradients for the sketch,
    then again for the leaf sums once the partition is known) and written
    once (the update); the targets read twice; the histograms
    (`hist_work`); one code per row per level to route; the eval scores
    read and written once and the eval targets and one code per eval row
    per level read once.  Flops: the sketch ``G @ Pi`` (2 n d k; none when
    not ``sketched``, where the histograms take ``G`` itself and ``k`` is
    ``d``), the leaf sums of G and H (2 n d), the update (2 n d), the eval
    update (2 n_eval d) and the histogram adds.
    """
    y_row = d * F32 if dense_targets else F32
    f_bytes = 3 * n * d * F32 + 2 * n * y_row + n * depth * CODE_BYTES
    e_bytes = 2 * n_eval * d * F32 + n_eval * y_row \
        + n_eval * depth * CODE_BYTES
    flops = 2.0 * n * d * k * sketched + 4.0 * n * d + 2.0 * n_eval * d
    return Work(flops=flops, bytes=f_bytes + e_bytes) \
        + hist_work(leaf_cover, m, k, n_bins)

