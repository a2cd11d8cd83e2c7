"""Pluggable cohort-execution backends: HOW a cohort of client updates runs.

Every MMFL hot path — the sync trainer's per-round per-task update, the
async engine's flush groups, and the production arch round loop — reduces
to the same two steps: *run a cohort of client-local updates from one set
of global params*, then *aggregate the stacked updates with per-client
weights*. This module makes that pair a first-class, registry-dispatched
API (the way ``spec.py`` did for scenarios), so a performance improvement
is a new backend, not a new engine fork:

    @register_backend("my_backend")
    class MyBackend(VmapBackend): ...

    spec.runtime.backend = "my_backend"      # or --backend on the CLI

Contract
--------
``run_cohort(task_state, client_batch, rng) -> CohortResult`` executes
``task_state.local_fn`` — ``(params, key, *client_data) -> (update, loss)``
for ONE client — once per entry of ``client_batch`` and stacks the results
along a leading client axis. ``local_fn`` must derive all randomness from
its ``key`` argument (the engines key by ``fold_in(round_key, client_id)``),
so every backend computes the identical per-client result and differs only
in *how* the cohort is scheduled:

- ``serial``  — reference: one jitted call per client, Python loop.
  Bit-exact with the pre-backend drivers (the fold_in keying makes each
  client's update independent of its cohort neighbours).
- ``vmap``    — the cohort batched into ONE jitted ``jax.vmap`` step over
  stacked per-client data, padded to the next power of two so XLA compiles
  at most log2(K)+1 cohort shapes per task.
- ``sharded`` — the vmap step with the client axis sharded across a
  ``launch/mesh.py`` device mesh (pure data parallelism over clients),
  aggregated as per-device partial sums plus one all-reduce; falls back
  to ``vmap`` on single-device hosts.

``aggregate(stacked_updates, weights, normalizer=None)`` computes the
weighted sum ``sum_k (w_k / max(normalizer, 1e-12)) * update_k`` per leaf
(``normalizer`` defaults to ``weights.sum()`` — plain FedAvg; the async
engine passes staleness-discounted weights with the undiscounted sum).
Compiled backends route it through the Pallas ``kernels/fedavg.py`` kernel
when a compiled platform is available (TPU/GPU); on CPU the jnp path is
both the oracle and the fast path.

Instances are stateless: jitted transforms live in module-level caches
keyed by the ``local_fn`` object, so repeated engine construction (sweeps,
benchmarks) reuses compilations as the pre-backend module-level jits did.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import BACKENDS, register_backend

# ---------------------------------------------------------------- data model


@dataclass
class CohortTask:
    """What a cohort trains: global state + the one-client update rule.

    ``params`` is whatever pytree ``local_fn`` trains (model params for
    FedAvg cohorts; a ``(params, opt_state)`` tuple for fused server-step
    tasks). ``local_fn(params, key, *client_data) -> (update, loss)`` must
    be a STABLE object across rounds — backends key their jit caches on it.
    """

    name: str
    params: Any
    local_fn: Callable


@dataclass
class ClientBatch:
    """One cohort's stacked per-client inputs (leading axis = cohort size).

    ``keys`` is a stacked PRNG-key array (or None for deterministic local
    steps); every entry of ``data`` is a pytree whose leaves carry the
    cohort axis first.
    """

    client_ids: np.ndarray
    keys: Any
    data: Tuple[Any, ...] = ()

    def __post_init__(self):
        self.client_ids = np.asarray(self.client_ids, np.int64)

    def __len__(self) -> int:
        return len(self.client_ids)


@dataclass
class CohortResult:
    """Stacked cohort output: ``updates`` mirrors ``local_fn``'s update
    pytree with a leading cohort axis; ``losses`` is the per-client local
    loss (shape ``(n,)``)."""

    updates: Any
    losses: Any = None


@runtime_checkable
class ExecutionBackend(Protocol):
    """What every execution backend looks like to an engine:
    ``run_cohort(task_state: CohortTask, client_batch: ClientBatch, rng)``
    and ``aggregate(stacked_updates, weights, normalizer=None)``."""

    def run_cohort(self, task_state, client_batch, rng=None) -> CohortResult: ...

    def aggregate(self, stacked_updates, weights, normalizer=None): ...


def get_backend(backend) -> ExecutionBackend:
    """Resolve a backend from a registry key, class, or instance."""
    if isinstance(backend, str):
        backend = BACKENDS.get(backend)
    if isinstance(backend, type):
        backend = backend()
    return backend


# ------------------------------------------------------- shared jit caching

# process-wide: engines are rebuilt per scenario (sweeps, benchmarks), but
# their local_fns are module-cached, so compilations must outlive instances
_TRANSFORMS: dict = {}


def _jit_single(local_fn):
    got = _TRANSFORMS.get((local_fn, "single"))
    if got is None:
        got = jax.jit(local_fn)
        _TRANSFORMS[(local_fn, "single")] = got
    return got


def _jit_vmapped(local_fn, n_data: int):
    key = (local_fn, "vmap", n_data)
    got = _TRANSFORMS.get(key)
    if got is None:
        got = jax.jit(jax.vmap(local_fn, in_axes=(None, 0) + (0,) * n_data))
        _TRANSFORMS[key] = got
    return got


def _pad_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _pad_cohort(tree, n: int, padded: int):
    """Pad every leaf's leading axis from n to padded by repeating the last
    row — duplicate rows compute duplicate results and are sliced off, so
    padding never changes the kept entries."""
    if padded == n or tree is None:
        return tree

    def pad(leaf):
        reps = jnp.repeat(leaf[-1:], padded - n, axis=0)
        return jnp.concatenate([leaf, reps], axis=0)

    return jax.tree.map(pad, tree)


def _weighted_sum_jnp(stacked, norm):
    def avg(leaf):
        return jnp.tensordot(norm, leaf, axes=(0, 0)).astype(leaf.dtype)

    return jax.tree.map(avg, stacked)


def _norm_weights(weights, normalizer):
    w = jnp.asarray(weights, jnp.float32)
    denom = w.sum() if normalizer is None else jnp.asarray(normalizer, jnp.float32)
    return w / jnp.maximum(denom, 1e-12)


# ------------------------------------------------------------------ backends


@register_backend("serial")
class SerialBackend:
    """Reference backend: one jitted call per client, in cohort order.

    This is the semantics every other backend must reproduce (≤1e-6): the
    fold_in-keyed ``local_fn`` makes each client's update independent of
    its neighbours, so batching/sharding are pure scheduling choices.
    """

    name = "serial"

    def run_cohort(self, task_state, client_batch, rng=None):
        fn = _jit_single(task_state.local_fn)
        updates, losses = [], []
        for i in range(len(client_batch)):
            key_i = None if client_batch.keys is None else client_batch.keys[i]
            data_i = tuple(jax.tree.map(lambda leaf: leaf[i], d) for d in client_batch.data)
            upd, loss = fn(task_state.params, key_i, *data_i)
            updates.append(upd)
            losses.append(loss)
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *updates)
        return CohortResult(stacked, jnp.stack(losses))

    def aggregate(self, stacked_updates, weights, normalizer=None):
        return _weighted_sum_jnp(stacked_updates, _norm_weights(weights, normalizer))


@register_backend("vmap")
class VmapBackend:
    """The cohort as ONE jitted ``jax.vmap`` step over stacked per-client
    data. Cohorts are padded to the next power of two (repeating the last
    client) so XLA compiles at most log2(K)+1 shapes per task; fold_in
    keying makes the padded rows exact duplicates, sliced off on return.
    """

    name = "vmap"

    def _prepare(self, client_batch):
        n = len(client_batch)
        padded = _pad_pow2(n)
        keys = _pad_cohort(client_batch.keys, n, padded)
        data = tuple(_pad_cohort(d, n, padded) for d in client_batch.data)
        return n, keys, data

    def run_cohort(self, task_state, client_batch, rng=None):
        n, keys, data = self._prepare(client_batch)
        fn = _jit_vmapped(task_state.local_fn, len(data))
        updates, losses = fn(task_state.params, keys, *data)
        return CohortResult(jax.tree.map(lambda leaf: leaf[:n], updates), losses[:n])

    def aggregate(self, stacked_updates, weights, normalizer=None):
        return _cohort_sum()(stacked_updates, _norm_weights(weights, normalizer))


@register_backend("sharded")
class ShardedBackend(VmapBackend):
    """The vmap step with the cohort axis sharded across a device mesh
    (``launch/mesh.py``) — pure data parallelism over clients, the
    multi-device dispatch of flush groups named by the ROADMAP. Falls back
    to ``vmap`` on single-device hosts.
    """

    name = "sharded"

    def __init__(self):
        self._mesh = None

    def _cohort_mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_cohort_mesh

            self._mesh = make_cohort_mesh()
        return self._mesh

    def run_cohort(self, task_state, client_batch, rng=None):
        if jax.device_count() <= 1 or len(client_batch) < 2:
            return super().run_cohort(task_state, client_batch, rng)
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = self._cohort_mesh()
        n_shards = mesh.devices.size
        n = len(client_batch)
        # pad the cohort axis to a multiple of the mesh size (duplicate
        # rows, sliced off on return) so the shard split is even
        padded = max(_pad_pow2(n), n_shards)
        padded += (-padded) % n_shards
        cohort_sharding = NamedSharding(mesh, PartitionSpec("clients"))
        replicated = NamedSharding(mesh, PartitionSpec())
        params = jax.device_put(task_state.params, replicated)
        keys = _pad_cohort(client_batch.keys, n, padded)
        keys = None if keys is None else jax.device_put(keys, cohort_sharding)
        data = tuple(
            jax.device_put(_pad_cohort(d, n, padded), cohort_sharding) for d in client_batch.data
        )
        fn = _jit_vmapped(task_state.local_fn, len(data))
        updates, losses = fn(params, keys, *data)
        return CohortResult(jax.tree.map(lambda leaf: leaf[:n], updates), losses[:n])

    def aggregate(self, stacked_updates, weights, normalizer=None):
        """Each device reduces its own slice of the cohort, then one
        all-reduce of the N-sized partial sums: the K x N cohort is never
        gathered. A Pallas kernel cannot be partitioned by the compiler,
        so the per-device reduce runs inside ``shard_map``."""
        if jax.device_count() <= 1:
            return super().aggregate(stacked_updates, weights, normalizer)
        norm = _norm_weights(weights, normalizer)
        mesh = self._cohort_mesh()
        k = norm.shape[0]
        padded = k + (-k) % mesh.devices.size
        # padded rows repeat the last client at weight 0: they add nothing
        stacked_updates = _pad_cohort(stacked_updates, k, padded)
        norm = jnp.pad(norm, (0, padded - k))
        return _sharded_fold(mesh, _cohort_sum())(stacked_updates, norm)


# ----------------------------------------------------- compiled aggregation


def _cohort_sum():
    """The weighted cohort sum for this platform: the Pallas kernel where
    it compiles (TPU/GPU). Interpret-mode Pallas is a correctness oracle,
    not a fast path, so on CPU the jnp weighted sum IS the compiled path."""
    return _weighted_sum_jnp if jax.default_backend() == "cpu" else _pallas_aggregate


def _sharded_fold(mesh, local):
    """jit of ``local(stacked, norm)`` over each device's slice of the
    ``"clients"`` axis of ``mesh``, summed across devices."""
    from jax.sharding import PartitionSpec

    key = (mesh, local, "fold")
    got = _TRANSFORMS.get(key)
    if got is None:
        clients = PartitionSpec("clients")
        # check_vma=False: a pallas_call's out_shape carries no
        # per-axis variance annotation for the checker to read
        got = jax.jit(jax.shard_map(
            lambda x, w: jax.lax.psum(local(x, w), "clients"), mesh=mesh,
            in_specs=(clients, clients), out_specs=PartitionSpec(),
            check_vma=False))
        _TRANSFORMS[key] = got
    return got


def _pallas_aggregate(stacked_updates, norm):
    """Route the weighted sum through the Pallas fedavg kernel: flatten the
    cohort to (K, N), one MXU matvec per parameter block, unflatten."""
    from jax.flatten_util import ravel_pytree

    from repro.kernels import fedavg_aggregate

    flat = jax.vmap(lambda p: ravel_pytree(p)[0])(stacked_updates)
    template = jax.tree.map(lambda leaf: leaf[0], stacked_updates)
    _, unravel = ravel_pytree(template)
    # keep the f32 weights as-is: the kernel promotes mixed-precision
    # inputs to the common dtype (demoting normalised weights to a bf16
    # cohort dtype, the pre-fix behaviour, rounds them before the matvec)
    agg = fedavg_aggregate(flat, norm)
    return jax.tree.map(lambda ref, new: jnp.asarray(new, ref.dtype), template, unravel(agg))


__all__ = [
    "BACKENDS",
    "ClientBatch",
    "CohortResult",
    "CohortTask",
    "ExecutionBackend",
    "SerialBackend",
    "ShardedBackend",
    "VmapBackend",
    "get_backend",
    "register_backend",
]
