"""The work a fused IVF scan needs, from the live rows alone.

For one query and each list it probes, the scan has to read the slabs that
hold the list's live rows: ``ceil(rows / C)`` slabs of ``C`` slots, each
slot a ``D``-wide float32 payload plus its metadata (id, norm, validity
bit). It scores each slot with ``2 D`` operations. The count never uses the
padded slab table, ``max_chain`` or ``n_slabs``, so it is the same whatever
layout implements the scan: a kernel that skips empty table entries gains
honestly.
"""
from __future__ import annotations

import numpy as np

META_BYTES_PER_SLOT = 4 + 4 + 1 / 8      # int32 id, float32 norm, one bit


def scan_work(list_rows: np.ndarray, probed: np.ndarray, dim: int,
              capacity: int) -> tuple[float, float]:
    """``(bytes, flops)`` of scanning ``probed`` (``[Q, nprobe]`` list ids
    of the live queries) over lists holding ``list_rows`` live rows."""
    slabs = -(-np.asarray(list_rows, np.int64) // capacity)
    slots = float(slabs[np.asarray(probed)].sum()) * capacity
    n_q = np.asarray(probed).shape[0]
    nbytes = slots * (4 * dim + META_BYTES_PER_SLOT) + n_q * 4 * dim
    return nbytes, 2.0 * dim * slots


def least_time_s(nbytes: float, flops: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tb = nbytes / peak["hbm_bytes_per_s"]
    tf = flops / peak["bf16_flops"]
    return (tb, "bytes") if tb >= tf else (tf, "flops")
