"""End-to-end MMFL simulation driver (paper Algorithm 1 + Section V).

Per global round:
  1. a fraction C of clients is active (uniformly at random);
  2. the allocator (FedFairMMFL / random / round-robin) assigns each active
     client to ONE task — restricted to tasks the client committed to via
     the recruitment auction (eligibility matrix), renormalising Eq. 4 per
     client over its eligible tasks;
  3. each task's selected clients run tau local SGD steps from the task's
     global params — dispatched through the pluggable ExecutionBackend
     (``api.backend``: serial reference, one vmapped compiled call, or a
     device-sharded cohort);
  4. the server aggregates with p_k weights and re-evaluates test accuracy,
     which feeds the next round's allocation (f_s = 1 - acc_s, as in the
     paper's experiments).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.aggregator import aggregator_from_config
from repro.api.backend import ClientBatch, CohortTask, get_backend
from repro.api.policy import (AllocationPolicy, LegacyStrategyPolicy,
                              RoundContext, RoundObservation,
                              stacked_delta_norms)
from repro.core.allocation import AllocationStrategy
from repro.fed.client import (accuracy, cohort_local_update_ids, init_mlp,
                              local_update)
from repro.fed.data import FedTask


def task_round_key(seed: int, task_idx: int, version: int):
    """PRNG key for (task, model-version) — version is the round index in
    the sync driver and the aggregation count in the async engine. Both
    drivers derive keys this way, so a cohort update is reproducible from
    (seed, task, version, client_id) alone."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), task_idx)
    return jax.random.fold_in(k, version)


def init_task_model(task: FedTask, key, hidden: int, depth: int,
                    deep_for=(), deep_depth: int = 3):
    """Model init for ONE task ("bigger model for the harder task", as the
    paper uses a ResNet for CIFAR)."""
    base = task.name.split("#")[0]
    d = deep_depth if base in deep_for else depth
    return init_mlp(key, task.train_x.shape[-1], hidden, task.n_classes,
                    depth=d)


def init_task_models(tasks: List[FedTask], key, hidden: int, depth: int,
                     deep_for=(), deep_depth: int = 3):
    """Per-task model init shared by the sync trainer and async engine:
    task s always gets key fold_in(key, s), so both drivers start from
    identical models."""
    return [init_task_model(t, jax.random.fold_in(key, s), hidden, depth,
                            deep_for, deep_depth)
            for s, t in enumerate(tasks)]


def cohort_update(global_params, key, task: FedTask, client_ids,
                  tau: int, lr, batch_size: int):
    """Run tau local steps for the given clients of one task in ONE
    compiled call (library entry point; tests and examples use it as the
    reference cohort). Returns a cohort pytree with leading axis
    len(client_ids).

    client_ids is padded to the next power of two (repeating the last id)
    so XLA compiles at most log2(K)+1 cohort shapes per task instead of
    one per distinct cohort size; fold_in keying makes the padded rows
    exact duplicates, which are sliced off before returning.
    """
    ids = np.asarray(client_ids, np.int32)
    n = len(ids)
    padded = 1 << max(n - 1, 0).bit_length()
    if padded > n:
        ids = np.concatenate([ids, np.full(padded - n, ids[-1], np.int32)])
    cohort = cohort_local_update_ids(
        global_params, key, jnp.asarray(task.train_x),
        jnp.asarray(task.train_y), jnp.asarray(task.train_w),
        jnp.asarray(ids), tau, lr, batch_size)
    return jax.tree.map(lambda leaf: leaf[:n], cohort)


@functools.lru_cache(maxsize=None)
def fed_local_fn(tau: int, lr: float, batch_size: int):
    """The ONE-client update rule behind the ExecutionBackend API: tau
    local SGD steps (``fed.client.local_update``) returning
    ``(updated_params, loss)``. lru_cached so every trainer/adapter with
    the same hyper-parameters shares one function object — backends key
    their jit caches on it, so compilations survive engine reconstruction
    (sweeps, benchmarks)."""

    def local_fn(params, key, x, y, w):
        return local_update(params, key, x, y, w, tau, lr,
                            batch_size), jnp.zeros(())

    return local_fn


def fed_client_batch(task: FedTask, key, client_ids) -> ClientBatch:
    """Stacked per-client inputs for a FedTask cohort. Per-client keys are
    ``fold_in(round_key, client_id)`` — the property that makes a client's
    update independent of which other clients share the cohort, so every
    backend (and the sync/async drivers) computes identical results."""
    ids = np.asarray(client_ids, np.int32)
    keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(jnp.asarray(ids))
    if hasattr(task, "gather"):
        # lazily-materialized partitions (repro.pop.data.LazyFedTask):
        # rows are generated/cached on first dispatch instead of fancy-
        # indexing an eager (K, n_max, dim) tensor
        x, y, w = task.gather(ids)
        return ClientBatch(client_ids=ids, keys=keys,
                           data=(jnp.asarray(x), jnp.asarray(y),
                                 jnp.asarray(w)))
    return ClientBatch(
        client_ids=ids,
        keys=keys,
        data=(jnp.asarray(task.train_x[ids]), jnp.asarray(task.train_y[ids]),
              jnp.asarray(task.train_w[ids])))


@dataclass
class TrainConfig:
    rounds: int = 100
    alpha: float = 3.0
    participation: float = 0.35
    tau: int = 5
    lr: float = 0.1
    batch_size: int = 32
    hidden: int = 64
    depth: int = 2
    strategy: AllocationStrategy = AllocationStrategy.FEDFAIR
    seed: int = 0
    eval_every: int = 1
    # stragglers: each selected client fails to return its update with this
    # probability (paper §VII future-work: heterogeneous/stochastic client
    # resources). Failed clients simply drop out of the round's aggregation.
    dropout_prob: float = 0.0
    # "bigger model for the harder task" (paper uses a ResNet for CIFAR):
    deep_for: tuple = ("synth-cifar",)
    deep_depth: int = 3
    # cohort execution backend (api.backend BACKENDS key or instance)
    backend: str = "serial"
    # stateful allocation policy (api.policy); None wraps `strategy`
    # bit-exactly via LegacyStrategyPolicy
    policy: Optional[AllocationPolicy] = None
    # server aggregation rule (api.aggregator AGGREGATORS key); None
    # selects "fedavg" — the bit-exact legacy weighted mean
    aggregator: Optional[str] = None
    aggregator_options: dict = field(default_factory=dict)
    # client cost model (api.costmodel COST_MODELS key); None selects
    # "constant" (unit job cost). Sync rounds are a lockstep barrier, so
    # each round's simulated duration is the max over the cohort's
    # sampled latencies — the History.wall_clock_sim curve. A model's
    # dropout flag is ignored here (sync stragglers are `dropout_prob`).
    cost_model: Optional[str] = None
    cost_model_options: dict = field(default_factory=dict)
    # vectorized client population (repro.pop POPULATIONS key); None keeps
    # the legacy per-client state, "vectorized" is bit-exact with it
    population: Optional[str] = None
    population_options: dict = field(default_factory=dict)
    # mid-run checkpointing (checkpoint/checkpoint.py, engine kind
    # "sync_fed"): every `checkpoint_every` rounds the bounded state
    # (params, policy/incentive/aggregator/cost-model state, RNG) is
    # saved while the round curves stream into the append-only
    # history.jsonl sidecar; resume=True restores the latest step,
    # replays the sidecar, and continues round-for-round identically
    # to an uninterrupted run
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 10
    checkpoint_keep: int = 3
    resume: bool = False


@dataclass
class History:
    acc: np.ndarray                     # (rounds, S)
    alloc_counts: np.ndarray            # (rounds, S)
    alloc: Optional[np.ndarray] = None  # (rounds, K) task id / -1 idle
    # (rounds,) cumulative simulated clock (cost-model round durations)
    wall_clock_sim: Optional[np.ndarray] = None
    min_acc: np.ndarray = field(init=False)
    var_acc: np.ndarray = field(init=False)

    def __post_init__(self):
        self.min_acc = self.acc.min(axis=1)
        self.var_acc = self.acc.var(axis=1)


class MMFLTrainer:
    def __init__(self, tasks: List[FedTask], cfg: TrainConfig,
                 eligibility: Optional[np.ndarray] = None,
                 incentive=None):
        self.tasks = tasks
        self.cfg = cfg
        self.S = len(tasks)
        self.K = tasks[0].n_clients
        assert all(t.n_clients == self.K for t in tasks)
        # eligibility[i, s]: client i willing to train task s (auction
        # winners). Default: everyone trains everything (Section III).
        self.elig = (np.ones((self.K, self.S), bool)
                     if eligibility is None else eligibility.astype(bool))
        self.backend = get_backend(cfg.backend)
        self._local_fn = fed_local_fn(cfg.tau, cfg.lr, cfg.batch_size)
        self._names = [t.name for t in tasks]
        # allocation dispatches through the policy object; sampling (and
        # the RNG stream) stays here, so legacy strategies are bit-exact
        self.policy = (cfg.policy if cfg.policy is not None
                       else LegacyStrategyPolicy(cfg.strategy))
        # per-round re-recruitment (api.policy.IncentiveMechanism); the
        # legacy one_shot mechanism never updates after round 0
        self.incentive = incentive
        # server aggregation rule (api.aggregator); "fedavg" reproduces
        # the pre-aggregator weighted mean bit-exactly. Server state is
        # initialised inside run() so repeated run() calls start fresh.
        self.aggregator = aggregator_from_config(
            cfg.aggregator, cfg.aggregator_options, backend=self.backend)
        # client cost model (api.costmodel): per-round simulated clock;
        # "constant" gives every job unit cost. reset() happens in run()
        # (its own seed + 3 stream; repeated run() calls start fresh).
        # With a population configured, the population OWNS the cost model
        # (and all other per-client state); the trainer aliases it so the
        # reset/sample call sites below are unchanged.
        if cfg.population is None and cfg.population_options:
            raise ValueError(
                "population_options were given without a population; "
                "name one (e.g. 'vectorized') or drop the options")
        self.population = None
        if cfg.population is not None:
            from repro.pop import get_population
            self.population = get_population(
                cfg.population, cfg.population_options,
                n_clients=self.K, n_tasks=self.S, seed=cfg.seed,
                cost_model=cfg.cost_model,
                cost_model_options=cfg.cost_model_options)
            self.cost_model = self.population.cost_model
            self.elig = self.population.set_eligibility(self.elig)
        else:
            from repro.api.costmodel import get_cost_model
            if cfg.cost_model is None and cfg.cost_model_options:
                raise ValueError(
                    "cost_model_options were given without a cost_model; "
                    "name one (e.g. 'device_tiers') or drop the options")
            self.cost_model = get_cost_model(cfg.cost_model or "constant",
                                             cfg.cost_model_options)
        # construction-time snapshots: run() restores them so repeated
        # run() calls are identical (the pre-policy contract) even though
        # policy/incentive/eligibility state mutates during a run
        self._elig0 = self.elig.copy()
        self._policy_state0 = self.policy.state_dict()
        self._incentive_state0 = (None if incentive is None
                                  else incentive.state_dict())

    def _init_models(self, key):
        return init_task_models(self.tasks, key, self.cfg.hidden,
                                self.cfg.depth, self.cfg.deep_for,
                                self.cfg.deep_depth)

    def _set_elig(self, elig) -> np.ndarray:
        """Adopt a (K, S) eligibility matrix, mirroring it into the
        population's struct-of-arrays when one is configured."""
        elig = np.asarray(elig, bool)
        if self.population is not None:
            return self.population.set_eligibility(elig)
        return elig

    def _allocate(self, rng, losses, round_idx):
        """Per-client task assignment, honouring eligibility. The policy
        supplies the per-task probabilities (None selects round-robin);
        sampling consumes THIS rng, never the policy's."""
        cfg = self.cfg
        m = max(1, int(round(cfg.participation * self.K)))
        active = rng.choice(self.K, size=m, replace=False)
        alloc = -np.ones(self.K, np.int64)      # -1: idle
        p = self.policy.allocate(RoundContext(
            round=round_idx, task_names=self._names, losses=losses,
            alpha=cfg.alpha, n_clients=self.K, eligibility=self.elig))
        if p is None:                           # round robin
            order = rng.permutation(active)
            nxt = round_idx
            for i in order:
                elig = np.where(self.elig[i])[0]
                if len(elig) == 0:
                    continue
                # next task in RR order that i is eligible for
                for off in range(self.S):
                    s = (nxt + off) % self.S
                    if self.elig[i, s]:
                        alloc[i] = s
                        nxt = nxt + off + 1
                        break
            return alloc
        for i in active:
            pe = p * self.elig[i]
            tot = pe.sum()
            if tot <= 0:
                continue
            alloc[i] = rng.choice(self.S, p=pe / tot)
        return alloc

    def run(self, verbose: bool = False) -> History:
        cfg = self.cfg
        # reproducibility: every run() starts from the construction-time
        # allocation/incentive state, so run() twice == run() once twice
        self.elig = self._set_elig(self._elig0.copy())
        self.policy.load_state(self._policy_state0)
        if self.incentive is not None:
            self.incentive.load_state(self._incentive_state0)
        rng = np.random.default_rng(cfg.seed)
        params = self._init_models(jax.random.PRNGKey(cfg.seed))
        server_state = [self.aggregator.init(p) for p in params]
        self.cost_model.reset(
            self.K, self.S, np.random.default_rng(cfg.seed + 3),
            task_sizes=[float(sum(np.size(leaf)
                                  for leaf in jax.tree.leaves(p)))
                        for p in params])
        clock = 0.0
        accs = np.zeros(self.S)
        for s, t in enumerate(self.tasks):
            accs[s] = float(accuracy(params[s], t.test_x, t.test_y))
        acc_hist, alloc_hist, assign_hist, clock_hist = [], [], [], []
        need_norms = getattr(self.policy, "wants_update_norms", False)
        ckpt, start_round = None, 0
        if cfg.checkpoint_dir:
            from repro.checkpoint import CheckpointManager
            if len(set(self._names)) != len(self._names):
                raise ValueError(
                    "checkpointing keys task pytrees by name; rename "
                    f"the duplicated tasks in {self._names!r} (e.g. "
                    "'synth-mnist#1') or drop checkpoint_dir")
            ckpt = CheckpointManager(cfg.checkpoint_dir,
                                     keep=cfg.checkpoint_keep)
            # shared resume preamble (CheckpointManager.begin): resume
            # gate, foreign-engine guard, sidecar truncation + replay,
            # stale-step clear
            hit = ckpt.begin("sync_fed", cfg.resume)
            if hit is not None:
                coord = hit.coordinator
                for s, t in enumerate(self.tasks):
                    tree = hit.tasks[t.name]
                    params[s] = jax.tree.map(jnp.asarray, tree["params"])
                    srv = tree.get("server_state")
                    server_state[s] = (
                        jax.tree.map(jnp.asarray, srv)
                        if srv is not None
                        else self.aggregator.init(params[s]))
                self.aggregator.load_state(coord["aggregator"])
                self.policy.load_state(coord["policy"])
                self.elig = self._set_elig(
                    np.asarray(coord["eligibility"], bool))
                if self.incentive is not None and "incentive" in coord:
                    self.incentive.load_state(coord["incentive"])
                if self.population is not None and "population" in coord:
                    self.population.validate_config(coord["population"])
                rng.bit_generator.state = coord["rng"]
                self.cost_model.load_state(coord["cost_model"])
                accs = np.asarray(coord["accs"], np.float64)
                clock = float(coord["clock"])
                # replayed sidecar records rebuild the pre-checkpoint
                # curves, so the History covers the WHOLE run
                for rec in hit.history:
                    if rec.get("kind") != "round":
                        continue
                    acc_hist.append(np.asarray(rec["acc"], np.float64))
                    alloc_hist.append(np.asarray(rec["counts"], np.int64))
                    assign_hist.append(np.asarray(rec["alloc"], np.int64))
                    clock_hist.append(float(rec["wall_clock"]))
                start_round = hit.step
                if verbose:
                    print(f"resumed from round {hit.step}")
        for r in range(start_round, cfg.rounds):
            losses = np.maximum(1.0 - accs, 1e-6)   # paper: use test acc
            if self.incentive is not None:
                upd = self.incentive.recruit(RoundContext(
                    round=r, task_names=self._names, losses=losses,
                    alpha=cfg.alpha, n_clients=self.K,
                    eligibility=self.elig))
                if upd is not None:
                    self.elig = self._set_elig(upd.eligibility)
            alloc = self._allocate(rng, losses, r)
            if cfg.dropout_prob > 0:
                failed = rng.random(self.K) < cfg.dropout_prob
                alloc = np.where(failed, -1, alloc)
            counts = np.array([(alloc == s).sum() for s in range(self.S)])
            norms = np.full(self.S, np.nan) if need_norms else None
            # lockstep barrier: the round costs its slowest sampled
            # (client, task) latency ("constant": unit cost per job)
            round_time = 0.0
            for s, t in enumerate(self.tasks):
                sel_ids = np.where(alloc == s)[0]
                if len(sel_ids) == 0:
                    continue
                if self.population is not None:
                    # cohort-batched latency sampling (same stream order)
                    totals, _ = self.population.sample_latencies(
                        sel_ids, s, 1.0, times=clock)
                    round_time = max(round_time, float(totals.max()))
                else:
                    for i in sel_ids:
                        round_time = max(
                            round_time,
                            self.cost_model.sample_latency(
                                int(i), s, 1.0, time=clock).total)
                # cohort execution + aggregation dispatch through the
                # pluggable backend (serial == pre-backend trace bit-exact)
                res = self.backend.run_cohort(
                    CohortTask(t.name, params[s], self._local_fn),
                    fed_client_batch(t, task_round_key(cfg.seed, s, r),
                                     sel_ids))
                if need_norms:
                    norms[s] = float(
                        stacked_delta_norms(res.updates, params[s]).mean())
                # the aggregator folds the cohort (fedavg: the direct
                # backend weighted mean, bit-exact with the legacy trace)
                params[s], server_state[s] = self.aggregator.aggregate_params(
                    params[s], res.updates, jnp.asarray(t.p_k[sel_ids]),
                    server_state[s])
                accs[s] = float(accuracy(params[s], t.test_x, t.test_y))
            self.policy.observe(RoundObservation(
                round=r, task_names=self._names,
                losses=np.maximum(1.0 - accs, 1e-6), alloc_counts=counts,
                update_norms=norms))
            acc_hist.append(accs.copy())
            alloc_hist.append(counts)
            assign_hist.append(alloc.copy())
            clock += round_time
            clock_hist.append(clock)
            if ckpt is not None:
                # round curves stream into the append-only sidecar
                # (buffered; the next save fsyncs + commits the offset)
                ckpt.append_history({
                    "kind": "round",
                    "acc": [float(a) for a in accs],
                    "counts": [int(c) for c in counts],
                    "alloc": [int(x) for x in alloc],
                    "wall_clock": float(clock),
                })
                if (cfg.checkpoint_every > 0
                        and (r + 1) % cfg.checkpoint_every == 0):
                    trees = {}
                    for s2, t2 in enumerate(self.tasks):
                        trees[t2.name] = {"params": params[s2]}
                        if server_state[s2] is not None:
                            trees[t2.name]["server_state"] = \
                                server_state[s2]
                    coord_payload = {
                        "policy": self.policy.state_dict(),
                        "eligibility": np.asarray(self.elig,
                                                  bool).tolist(),
                        "rng": rng.bit_generator.state,
                        "accs": [float(a) for a in accs],
                        "clock": float(clock),
                        "aggregator": self.aggregator.state_dict(),
                        "cost_model": self.cost_model.state_dict(),
                    }
                    if self.population is not None:
                        coord_payload["population"] = \
                            self.population.config_record()
                    if self.incentive is not None:
                        coord_payload["incentive"] = \
                            self.incentive.state_dict()
                    ckpt.save(r + 1, trees,
                              coordinator_state=coord_payload,
                              engine_kind="sync_fed")
            if verbose and (r + 1) % 10 == 0:
                print(f"  round {r+1:4d} accs="
                      + " ".join(f"{a:.3f}" for a in accs)
                      + f" min={accs.min():.3f}")
        if ckpt is not None:
            ckpt.close()
        self.params = params    # final per-task models (RunResult parity)
        return History(np.array(acc_hist), np.array(alloc_hist),
                       alloc=np.array(assign_hist),
                       wall_clock_sim=np.asarray(clock_hist, np.float64))
