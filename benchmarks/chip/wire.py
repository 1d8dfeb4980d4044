"""Bytes on the wire of one federated round, from the counts the round
returns: the hub-and-spoke cost model of the paper's section 2.1.

A payload of ``nnz`` of ``n`` entries is sent sparse, a value and an index
per entry, or dense, a value per entry, whichever is smaller. Each sampled
client uploads its payload; the server unicasts the broadcast to each of
them. Host float64 throughout.
"""

from __future__ import annotations

import numpy as np

VALUE_BYTES = 4     # float32 values
INDEX_BYTES = 4     # int32 indices


def payload_bytes(nnz, n: int) -> np.ndarray:
    nnz = np.asarray(nnz, np.float64)
    return np.minimum(nnz * (VALUE_BYTES + INDEX_BYTES), float(n) * VALUE_BYTES)


def round_bytes(upload_nnz, download_nnz, n: int) -> float:
    """Upload plus download bytes of one round; ``upload_nnz`` holds one
    count per sampled client."""
    upload_nnz = np.asarray(upload_nnz, np.float64)
    up = float(np.sum(payload_bytes(upload_nnz, n)))
    down = float(payload_bytes(download_nnz, n)) * upload_nnz.size
    return up + down
