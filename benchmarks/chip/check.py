"""Whether a run is `correct`: the timed path's first three steps of the
configuration's task against the plain reference (``reference.py``).

Compared, each against its own limit from ``limits/<workload>.json``:

- ``loss_gap``: the largest relative gap between a local loss the cohort
  returned and the reference's, over every client row of the three steps;
- ``grad_norm_gap``: the first step's gradient as the optimizer got it,
  by the worst leaf: |norm(program) - norm(reference)| over the larger of
  the reference's norm of that leaf and of the median leaf;
- ``change_norm_gap``: the same for the params' change over three steps,
  leaving out leaves whose reference gradient is under a thousandth of
  the median leaf's (they move by round-off alone);
- ``gain_change_gap``: the same for the model family's norm gains alone
  (its ``GAINS``), each against its own norm: weights near 1, where
  bfloat16's resolution (2**-7) is coarser than an Adam step of 3e-3,
  so that a change of storage
  precision shows here when it shows in no norm over a whole model;
- ``foreign_rows``: rows the cohort trained on that are not rows of the
  seed's token shards (exact: limit 0).

Every number is computed; a cell compares those its limits file lists.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import reference

NOUGHT = 1e-3      # a leaf's gradient under this share of the median: round-off only


def _rows(tokens: np.ndarray, seq: int) -> np.ndarray:
    return np.asarray(tokens).reshape(-1, seq)


def replay(cfg: dict, tr: dict, rec, words, dtype=np.float32) -> dict:
    """The reference's losses (in the recorded order), first-step gradient
    norms and three-step change norms, from the seed's weights and the
    recorded rows; with them the names of the family's gains."""
    ref = reference.reference_for(cfg, dtype)
    make = reference.weights_fn(cfg)
    p0 = ref.cast(make(words))
    p, versions = p0, [p0]
    losses, grad = [], None
    if tr["mode"] == "sync" and tr["tau"] > 1:
        for k, (g,) in enumerate(rec.steps):
            rows = _rows(g["tokens"], tr["seq"])
            p, ls = ref.fedavg_round(p, rows, np.ones(len(rows)), tr["tau"], tr["local_lr"])
            losses.extend(ls)
            if k == 0:
                grad = reference_norms(p, p0)
                p0 = None
                versions.clear()
    elif tr["mode"] == "sync":
        p0 = None
        versions.clear()
        state = ref.adamw_init(p)
        for k, (g,) in enumerate(rec.steps):
            rows = _rows(g["tokens"], tr["seq"])
            w = np.full(len(rows), 1.0 / len(rows))
            p, state, loss, gk = ref.adamw_step(p, state, rows, w, tr["optimizer"])
            losses.append(loss)
            if k == 0:
                grad = reference_norms(gk)
    else:
        srv = dict(tr["aggregator_options"], server_lr=tr["server_lr"])
        state = ref.fedadam_init(p, srv["eps"])
        for k, groups in enumerate(rec.steps):
            deltas, stale = [], []
            for g in sorted(groups, key=lambda g: g["version"]):
                base = versions[g["version"]]
                for toks in g["tokens"]:
                    pc, loss = ref.sgd_local(base, _rows(toks, tr["seq"]), tr["tau"], tr["local_lr"])
                    deltas.append(jax.tree.map(lambda a, b: a - b, pc, base))
                    stale.append(k - g["version"])
                    losses.append(loss)
            p, state, d = ref.fedadam_flush(p, state, deltas, np.ones(len(deltas)), stale,
                                            tr["beta"], srv)
            del deltas
            versions.append(p)
            if k == 0:
                grad = reference_norms(d)
    p0 = None
    versions.clear()
    return {"losses": np.asarray(losses, np.float64), "grad": grad,
            "change": reference_norms(p, ref.cast(make(words))),
            "gains": reference.family(cfg).GAINS}


def reference_norms(a, b=None) -> np.ndarray:
    """Per-leaf Euclidean norms of ``a`` (or of ``a - b``), in float32."""
    f32 = jnp.float32
    leaves = [x.astype(f32) for x in jax.tree.leaves(a)]
    if b is not None:
        leaves = [x - y.astype(f32) for x, y in zip(leaves, jax.tree.leaves(b))]
    return np.asarray([float(jnp.sqrt(jnp.sum(jnp.square(x)))) for x in leaves])


def program_losses(rec) -> np.ndarray:
    """The cohort's local losses in the order ``replay`` makes them."""
    out = []
    for groups in rec.steps:
        for g in sorted(groups, key=lambda g: g["version"] if g["version"] is not None else 0):
            out.extend(np.ravel(g["losses"]))
    return np.asarray(out, np.float64)


def worst_leaf_gap(got, want, counted=None) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = max(float(np.median(want)), 1e-30)
    gap = np.abs(got - want) / np.maximum(want, floor)
    if counted is not None:
        gap = gap[counted]
    return float(gap.max())


def foreign_rows(rec, data: np.ndarray, seq: int) -> int:
    known = {r.tobytes() for r in data.reshape(-1, data.shape[-1])[:, :seq].astype(np.int32)}
    return sum(r.astype(np.int32).tobytes() not in known
               for groups in rec.steps for g in groups for r in _rows(g["tokens"], seq))


def distinct_rows(rec, seq: int) -> bool:
    """Whether every step trained on rows that all differ."""
    for groups in rec.steps:
        rows = np.concatenate([_rows(g["tokens"], seq) for g in groups])
        if len(np.unique(rows, axis=0)) < len(rows):
            return False
    return True


def compare(got: dict, want: dict, leaves: list) -> dict:
    """The compared numbers from two sets of readings (program or control
    as ``got``, the float32 reference as ``want``, from ``replay``);
    ``leaves`` names the params' leaves in order."""
    counted = want["grad"] >= NOUGHT * np.median(want["grad"])
    gains = np.array([any(name.endswith(f"['{g}']") for g in want["gains"])
                      for name in leaves]) & counted
    gc, wc = np.asarray(got["change"], np.float64), np.asarray(want["change"], np.float64)
    lg, lw = got["losses"], want["losses"]
    return {
        "loss_gap": float(np.max(np.abs(lg - lw) / np.abs(lw))) if len(lg) == len(lw) else math.inf,
        "grad_norm_gap": worst_leaf_gap(got["grad"], want["grad"]),
        "change_norm_gap": worst_leaf_gap(got["change"], want["change"], counted),
        "gain_change_gap": float(np.max(np.abs(gc - wc)[gains] / wc[gains])),
    }


def program_readings(rec) -> dict:
    return {"losses": program_losses(rec), "grad": rec.grad_norms, "change": rec.change_norms}


def load_limits(root: Path, workload: str) -> dict:
    return json.loads((root / "limits" / f"{workload}.json").read_text())["limits"]


def judge(numbers: dict, limits: dict) -> bool:
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k] for k in limits)
