"""Faults planted under the timed path, to show that `correct` catches
them: the calibration reads them on the chip, the tests on the CPU.

A fault wraps the backend's ``run_cohort`` for the configuration's task:
``before`` may change what the cohort is given, ``after`` what it
returns. The harness records the cohort's input as the engine gave it,
before the fault, so the reference follows the engine's rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class Fault:
    def __init__(self, task: str):
        self.task = task

    def before(self, task_state, client_batch):
        return task_state, client_batch

    def after(self, task_state, res):
        return res


class HalfBatch(Fault):
    """Half of each cohort row block left out, the mean taken over the
    rest: the second half of the rows repeats the first."""

    def before(self, task_state, client_batch):
        if task_state.name != self.task:
            return task_state, client_batch
        import dataclasses

        def halve(x):
            axis = x.ndim - 2 if x.shape[-2] > 1 else 0
            n = x.shape[axis]
            keep = jax.lax.slice_in_dim(x, 0, n // 2, axis=axis)
            return jnp.concatenate([keep, keep] + ([] if n % 2 == 0 else [
                jax.lax.slice_in_dim(x, 0, 1, axis=axis)]), axis=axis)

        def plant(d):
            if isinstance(d, dict):
                return {k: (halve(v) if k in ("tokens", "labels") else v) for k, v in d.items()}
            return halve(d)

        data = (plant(client_batch.data[0]),) + tuple(client_batch.data[1:])
        return task_state, dataclasses.replace(client_batch, data=data)


class StateUnchanged(Fault):
    """The step returns the state it was given."""

    def after(self, task_state, res):
        if task_state.name != self.task:
            return res
        n = jax.tree.leaves(res.updates)[0].shape[0]
        same = jax.tree.map(lambda p: jnp.broadcast_to(p, (n,) + p.shape), task_state.params)
        return type(res)(same, res.losses)


def for_cell(cfg: dict) -> dict:
    """The faults of a one-chip training cell that need a run to read: a
    state left unchanged reads 1 by the change measure without one, and
    the exchange between chips exists only on several."""
    from cell import task_name

    return {"half_batch": HalfBatch(task_name(cfg))}
