"""Operations and bytes of the work the cells run, from shapes alone.

Model FLOPs and the length of one flat update are the model family's
(``families/<name>.py``, found by ``reference.family``); the bytes of the
server folds are the same for every family.
"""
from __future__ import annotations

import reference

F32_BYTES = 4


def matmul_params(cfg: dict) -> int:
    return reference.family(cfg).matmul_params(cfg)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return reference.family(cfg).train_flops_per_token(cfg, seq)


def trained_params(cfg: dict, padded_vocab: int) -> int:
    """Every trained float of the model as the system holds it (the
    vocabulary padded as it is stored): the length of one flat update."""
    return reference.family(cfg).trained_params(cfg, padded_vocab)


def fedavg_fold_bytes(k: int, n: int) -> int:
    """Weighted sum of a (K, N) f32 cohort: K*N read, N written."""
    return (k * n + n) * F32_BYTES


def fedadam_fold_bytes(k: int, n: int) -> int:
    """Fused FedAdam flush: K*N deltas and the N-sized m, v read; the
    N-sized update, m and v written."""
    return (k * n + 2 * n + 3 * n) * F32_BYTES
