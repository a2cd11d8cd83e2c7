"""Token shards of the cells' clients, made from the seed.

Every client holds ``shards`` sequences of ``seq`` tokens drawn uniformly
from its own band of half the vocabulary, so clients differ (non-iid) and
their losses differ. This follows the system's own synthetic data
(``repro.launch.train.make_dataset``), drawn here in bulk so that the
same seed gives the same shards whatever the system does.
"""
from __future__ import annotations

import numpy as np


def client_shards(seed: int, n_clients: int, shards: int, seq: int, vocab: int) -> np.ndarray:
    """(n_clients, shards, seq) int32 token ids."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    half = max(1, vocab // 2)
    lo = rng.integers(0, half, size=(n_clients, 1, 1))
    return (lo + rng.integers(0, half, size=(n_clients, shards, seq))).astype(np.int32)
