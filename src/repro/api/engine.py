"""`run_scenario`: one entry point for every MMFL run.

A ``ScenarioSpec`` resolves — through the registries — to a task family
(synthetic FedTask MLPs or production LM architectures), an optional
recruitment auction producing the eligibility matrix, and a runtime
(sync lockstep rounds or the async FedAST-style event engine). Both
runtimes sit behind the same ``Engine`` protocol and return the same
``RunResult``, so callers (CLI, benchmarks, sweeps) never branch on mode.

    result = run_scenario(ScenarioSpec(tasks=[TaskSpec("synth-mnist")]))
    result.fairness["min_acc"], result.to_json()
"""

from __future__ import annotations

import copy
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Protocol

import numpy as np

from repro import spans
from repro.api.backend import ClientBatch, CohortTask, get_backend, take_row
from repro.api.policy import (  # noqa: F401  (re-exported legacy names)
    BID_MODELS,
    RoundContext,
    build_eligibility,
    incentive_from_spec,
    policy_from_spec,
    stacked_delta_norms,
)
from repro.api.registry import (
    AGGREGATORS,
    ALLOCATORS,
    ARRIVAL_PROCESSES,
    BACKENDS,
    BUFFER_CONTROLLERS,
    COST_MODELS,
    INCENTIVES,
    POLICIES,
    POPULATIONS,
    TASK_FAMILIES,
    register_task_family,
)
from repro.api.spec import ScenarioSpec
from repro.core.fairness import fairness_report, time_to_accuracy_report
from repro.fed.async_engine import AsyncConfig, AsyncMMFLEngine, FedAsyncTask
from repro.fed.data import _RECIPES, make_synthetic_task, task_seed
from repro.fed.trainer import MMFLTrainer, TrainConfig


# ----------------------------------------------------------------- result


@dataclass
class RunResult:
    """What every scenario run returns, sync or async.

    ``loss`` is the per-eval prevailing f_s curve (1 - accuracy for
    synthetic tasks, eval loss for arch tasks); ``acc`` is present only
    when the family defines accuracy. ``time`` is virtual flush time for
    async runs (sync rounds have no time model — derive one from the
    ``alloc`` trace as exp9 does).
    """

    scenario: str
    mode: str
    task_names: List[str]
    loss: np.ndarray  # (T, S)
    acc: Optional[np.ndarray]  # (T, S) or None
    arrivals: np.ndarray  # (S,) total client updates per task
    alloc_counts: Optional[np.ndarray] = None  # (T, S) sync per-round
    time: Optional[np.ndarray] = None  # (T,) async virtual times
    virtual_time: float = 0.0
    wall_time: float = 0.0
    fairness: Dict[str, Any] = field(default_factory=dict)
    spec: Optional[ScenarioSpec] = None
    # traces / diagnostics
    alloc: Optional[np.ndarray] = None  # sync (T, K) assignment trace
    assignments: Optional[List] = None  # async (client, task) dispatch log
    staleness_mean: Optional[np.ndarray] = None
    versions: Optional[np.ndarray] = None
    # async (F, S) per-task buffer sizes after each flush (the buffer
    # controller's emission trajectory; constant rows under "static")
    buffer_sizes: Optional[np.ndarray] = None
    dropped: int = 0
    # cost-model simulated wall clock: (T,) cumulative per-round clock
    # for sync runs (round time = max over cohort latencies), the flush
    # event times for async runs. Every engine sets it.
    wall_clock_sim: Optional[np.ndarray] = None
    cost_dropouts: int = 0  # async jobs the cost model dropped entirely
    auction: Optional[Dict[str, Any]] = None
    params: Optional[List] = None  # final per-task model pytrees

    def __post_init__(self):
        if not self.fairness:
            self.fairness = self._fairness()

    def _fairness(self) -> Dict[str, Any]:
        if self.acc is not None and len(self.acc):
            rep = fairness_report(self.acc[-1])
            rep["worst_task"] = self.task_names[int(np.argmin(self.acc[-1]))]
            return rep
        if len(self.loss) == 0:
            return {}
        last = np.asarray(self.loss[-1], np.float64)
        return {
            "min_loss": float(last.min()),
            "max_loss": float(last.max()),
            "mean_loss": float(last.mean()),
            "var_loss": float(last.var()),
            "worst_task": self.task_names[int(np.argmax(last))],
        }

    @property
    def min_acc(self) -> np.ndarray:
        if self.acc is None:
            raise ValueError("this task family does not define accuracy")
        return self.acc.min(axis=1)

    @property
    def var_acc(self) -> np.ndarray:
        if self.acc is None:
            raise ValueError("this task family does not define accuracy")
        return self.acc.var(axis=1)

    def time_to_accuracy(self, target: float) -> Dict[str, Any]:
        """Per-task simulated time to first reach ``target`` accuracy,
        plus the cross-task fairness spread (max / variance) — see
        ``core.fairness.time_to_accuracy_report``. Reads the cost-model
        clock (``wall_clock_sim``; async virtual ``time`` as fallback,
        then the round index, for a RunResult built without either)."""
        if self.acc is None:
            raise ValueError("this task family does not define accuracy")
        times = self.wall_clock_sim
        if times is None:
            times = self.time
        if times is None:
            times = np.arange(1, len(self.acc) + 1, dtype=np.float64)
        return time_to_accuracy_report(times, self.acc, target,
                                       self.task_names)

    @property
    def final_loss(self) -> Dict[str, float]:
        if len(self.loss) == 0:
            return {}
        return {n: float(v) for n, v in zip(self.task_names, self.loss[-1])}

    def to_json(self) -> Dict[str, Any]:
        """JSON-native summary (curves + fairness), used by benchmarks."""

        def arr(a):
            return None if a is None else np.asarray(a).tolist()

        out = {
            "scenario": self.scenario,
            "mode": self.mode,
            "task_names": list(self.task_names),
            "loss": arr(self.loss),
            "acc": arr(self.acc),
            "time": arr(self.time),
            "arrivals": arr(self.arrivals),
            "alloc_counts": arr(self.alloc_counts),
            "virtual_time": float(self.virtual_time),
            "wall_time": float(self.wall_time),
            "wall_clock_sim": arr(self.wall_clock_sim),
            "dropped": int(self.dropped),
            "cost_dropouts": int(self.cost_dropouts),
            "versions": arr(self.versions),
            "buffer_sizes": arr(self.buffer_sizes),
            "final_buffer_sizes": (
                None
                if self.buffer_sizes is None or not len(self.buffer_sizes)
                else np.asarray(self.buffer_sizes)[-1].tolist()
            ),
            "fairness": self.fairness,
            "final_loss": self.final_loss,
        }
        if self.auction is not None:
            out["auction"] = self.auction
        if self.spec is not None:
            out["spec"] = self.spec.to_dict()
        return out


class Engine(Protocol):
    """What both runtimes look like to a caller: build from a spec, run,
    get a RunResult. No mode branching on the caller side."""

    def run(self, verbose: bool = False) -> RunResult: ...


# ------------------------------------------------------------- spec -> cfg


def _train_config(spec: ScenarioSpec) -> TrainConfig:
    rt, pop, al = spec.runtime, spec.clients, spec.allocation
    return TrainConfig(
        rounds=rt.rounds,
        alpha=al.alpha,
        participation=pop.participation,
        tau=rt.tau,
        lr=rt.lr,
        batch_size=rt.batch_size,
        hidden=rt.hidden,
        depth=rt.depth,
        strategy=ALLOCATORS.get(al.strategy),
        seed=spec.seed,
        eval_every=rt.eval_every,
        dropout_prob=pop.dropout_prob,
        deep_for=tuple(rt.deep_for),
        deep_depth=rt.deep_depth,
        backend=rt.backend,
        policy=policy_from_spec(spec.policy, al.strategy),
        aggregator=rt.aggregator,
        aggregator_options=dict(rt.aggregator_options),
        cost_model=rt.cost_model,
        cost_model_options=dict(rt.cost_model_options),
        population=pop.population,
        population_options=dict(pop.population_options),
        checkpoint_dir=rt.checkpoint_dir,
        checkpoint_every=rt.checkpoint_every,
        checkpoint_keep=rt.checkpoint_keep,
        resume=rt.resume,
    )


def _async_config(spec: ScenarioSpec) -> AsyncConfig:
    rt, pop, al = spec.runtime, spec.clients, spec.allocation
    return AsyncConfig(
        total_arrivals=rt.total_arrivals,
        buffer_size=rt.buffer_size,
        beta=rt.beta,
        server_lr=rt.server_lr,
        alpha=al.alpha,
        strategy=ALLOCATORS.get(al.strategy),
        speed_profile=pop.speed_profile,
        speed_spread=pop.speed_spread,
        slow_fraction=pop.slow_fraction,
        arrival_process=pop.arrival_process,
        arrival_options=dict(pop.arrival_options),
        max_staleness=rt.max_staleness,
        buffer_controller=rt.buffer_controller,
        buffer_controller_options=dict(rt.buffer_controller_options),
        aggregator=rt.aggregator,
        aggregator_options=dict(rt.aggregator_options),
        cost_model=rt.cost_model,
        cost_model_options=dict(rt.cost_model_options),
        population=pop.population,
        population_options=dict(pop.population_options),
        checkpoint_dir=rt.checkpoint_dir,
        checkpoint_every=rt.checkpoint_every,
        checkpoint_keep=rt.checkpoint_keep,
        resume=rt.resume,
        backend=rt.backend,
        tau=rt.tau,
        lr=rt.lr,
        batch_size=rt.batch_size,
        hidden=rt.hidden,
        depth=rt.depth,
        deep_for=tuple(rt.deep_for),
        deep_depth=rt.deep_depth,
        seed=spec.seed,
        policy=policy_from_spec(spec.policy, al.strategy),
    )


# ------------------------------------------------------------ sync engine


class SyncFedEngine:
    """The sync lockstep round loop (``MMFLTrainer``) behind the Engine
    protocol — identical configs produce identical Histories."""

    def __init__(self, spec: ScenarioSpec, tasks, eligibility=None, incentive=None):
        self.spec = spec
        self.trainer = MMFLTrainer(
            tasks, _train_config(spec), eligibility=eligibility, incentive=incentive
        )

    def run(self, verbose: bool = False) -> RunResult:
        h = self.trainer.run(verbose=verbose)
        return RunResult(
            scenario=self.spec.name,
            mode="sync",
            task_names=[t.name for t in self.trainer.tasks],
            loss=np.maximum(1.0 - h.acc, 1e-6),
            acc=h.acc,
            arrivals=h.alloc_counts.sum(axis=0),
            alloc_counts=h.alloc_counts,
            alloc=h.alloc,
            wall_clock_sim=h.wall_clock_sim,
            spec=self.spec,
            params=self.trainer.params,
        )


class AsyncEngineRunner:
    """The async FedAST-style engine behind the Engine protocol."""

    def __init__(self, spec: ScenarioSpec, engine: AsyncMMFLEngine, has_acc: bool):
        self.spec = spec
        self.engine = engine
        self.has_acc = has_acc

    def run(self, verbose: bool = False) -> RunResult:
        h = self.engine.run(verbose=verbose)
        return RunResult(
            scenario=self.spec.name,
            mode="async",
            task_names=[t.name for t in self.engine.tasks],
            loss=h.metric,
            acc=h.acc if self.has_acc else None,
            arrivals=h.arrivals,
            time=h.time,
            virtual_time=float(h.time[-1]) if len(h.time) else 0.0,
            staleness_mean=h.staleness_mean,
            versions=h.versions,
            buffer_sizes=h.buffer_sizes,
            dropped=h.dropped,
            wall_clock_sim=h.wall_clock_sim,
            cost_dropouts=h.cost_dropouts,
            assignments=h.assignments,
            spec=self.spec,
            params=self.engine._params,
        )


# ------------------------------------------------------------ task families


@register_task_family("synthetic")
class SyntheticFamily:
    """Class-conditional Gaussian FedTasks (``fed.data``). TaskSpec
    options: any ``make_synthetic_task`` kwarg (``n_range``, ``non_iid``,
    recipe overrides). Seeding matches ``standard_tasks`` exactly."""

    def build_tasks(self, spec: ScenarioSpec):
        # lazily-materialized partitions: with a population configured and
        # lazy_data on, client shards are generated on first dispatch from
        # per-client derived streams (repro.pop.data) — O(1) construction
        # in n_clients instead of an eager (K, n_max, dim) tensor. The
        # data stream differs from the eager path, so it is opt-in.
        lazy = spec.clients.population is not None and bool(
            spec.clients.population_options.get("lazy_data")
        )
        ctor = make_synthetic_task
        if lazy:
            from repro.pop import LazyFedTask

            ctor = LazyFedTask
        tasks = []
        for i, ts in enumerate(spec.tasks):
            base = ts.name.split("#")[0]
            if base not in _RECIPES:
                recipes = ", ".join(sorted(_RECIPES))
                raise KeyError(f"unknown synthetic task {ts.name!r}; recipes: {recipes}")
            kw = dict(_RECIPES[base])
            kw.update(ts.options)
            if "n_range" in kw:
                kw["n_range"] = tuple(kw["n_range"])
            tasks.append(
                ctor(
                    task_seed(spec.data_seed, i),
                    ts.name,
                    spec.clients.n_clients,
                    **kw,
                )
            )
        return tasks

    def sync_engine(self, spec: ScenarioSpec, eligibility=None, incentive=None) -> Engine:
        return SyncFedEngine(spec, self.build_tasks(spec), eligibility, incentive)

    def async_engine(self, spec: ScenarioSpec, eligibility=None, incentive=None) -> Engine:
        acfg = _async_config(spec)
        adapters = [FedAsyncTask(t, s, acfg) for s, t in enumerate(self.build_tasks(spec))]
        for a, ts in zip(adapters, spec.tasks):
            a.work = ts.work
        engine = AsyncMMFLEngine(adapters, acfg, eligibility, incentive)
        return AsyncEngineRunner(spec, engine, has_acc=True)


@register_task_family("arch")
class ArchFamily:
    """Production LM architectures (``launch.train``): per-arch sharded
    train steps on synthetic non-iid token shards. TaskSpec options:
    ``preset``, ``seq``, ``batch``, ``tau``, ``local_lr``, ``shards``."""

    def build_tasks(self, spec: ScenarioSpec):
        # lazy import: launch.train imports this package for its CLI
        from repro.launch.train import build_task, make_dataset

        tasks, data = {}, {}
        for i, ts in enumerate(spec.tasks):
            o = ts.options
            seq = o.get("seq", 64)
            tasks[ts.name] = build_task(
                ts.name,
                o.get("preset", "tiny"),
                seq,
                o.get("batch", 8),
                tau=o.get("tau", 1),
                local_lr=o.get("local_lr", 5e-3),
            )
            data[ts.name] = make_dataset(
                None,
                tasks[ts.name]["cfg"],
                spec.clients.n_clients,
                o.get("shards", 4),
                seq,
                seed=spec.data_seed + i,
            )
        return tasks, data

    def sync_engine(self, spec: ScenarioSpec, eligibility=None, incentive=None) -> Engine:
        tasks, data = self.build_tasks(spec)
        return ArchSyncEngine(spec, tasks, data, eligibility, incentive)

    def async_engine(self, spec: ScenarioSpec, eligibility=None, incentive=None) -> Engine:
        from repro.launch.train import ArchAsyncTask

        tasks, data = self.build_tasks(spec)
        adapters = []
        for i, ts in enumerate(spec.tasks):
            a = ArchAsyncTask(
                ts.name,
                i,
                tasks[ts.name],
                data[ts.name],
                tau=max(ts.options.get("tau", 1), 1),
                local_lr=ts.options.get("local_lr", 5e-3),
            )
            a.work = ts.work
            adapters.append(a)
        engine = AsyncMMFLEngine(adapters, _async_config(spec), eligibility, incentive)
        # ArchAsyncTask defines accuracy(): the history carries a real
        # next-token accuracy curve, so fairness unifies with synthetic
        return AsyncEngineRunner(spec, engine, has_acc=True)


class ArchSyncEngine:
    """The production sync round loop (formerly inlined in
    ``launch/train.py``): MMFLCoordinator allocation -> per-arch cohort
    dispatch through the ExecutionBackend API -> loss/accuracy report,
    with full-state checkpoint/resume (params, opt, coordinator round/RNG
    — so post-resume allocations match an uninterrupted run).

    tau>1 tasks run TRUE FedAvg: each cohort row's tau local SGD steps
    execute via ``backend.run_cohort`` and aggregate via
    ``backend.aggregate`` (the Pallas fedavg path on compiled platforms).
    tau<=1 tasks are the fused weighted-gradient server step — dispatched
    as a degenerate single-unit cohort so every engine shares one
    execution seam; ``take_row`` hands the row back as the new state.
    """

    def __init__(self, spec: ScenarioSpec, tasks, data, eligibility=None, incentive=None):
        from repro.api.aggregator import aggregator_from_config
        from repro.core.mmfl import MMFLCoordinator
        from repro.launch.train import make_arch_eval

        self.spec = spec
        self.tasks = tasks
        self.data = data
        self.names = [t.name for t in spec.tasks]
        self.backend = get_backend(spec.runtime.backend)
        # server aggregation rule; applies to tau>1 (true FedAvg) tasks —
        # tau<=1 tasks are the fused weighted-gradient server step, whose
        # adamw update is baked into the cohort itself
        self.aggregator = aggregator_from_config(
            spec.runtime.aggregator, spec.runtime.aggregator_options,
            backend=self.backend,
        )
        self._server_state = {
            a: (self.aggregator.init(tasks[a]["params"]) if tasks[a]["tau"] > 1 else None)
            for a in self.names
        }
        self._eval_acc = {a: make_arch_eval(tasks[a], data[a])[1] for a in self.names}
        # client cost model (api.costmodel): each round's simulated
        # duration is the max over the cohort's sampled latencies (the
        # lockstep barrier); "constant" gives every job unit cost. With a
        # population configured, the population owns the cost model (and
        # the eligibility struct-of-arrays) and the engine aliases it.
        self.population = None
        if spec.clients.population is not None:
            from repro.pop import get_population

            self.population = get_population(
                spec.clients.population,
                spec.clients.population_options,
                n_clients=spec.clients.n_clients,
                n_tasks=len(self.names),
                seed=spec.seed,
                cost_model=spec.runtime.cost_model,
                cost_model_options=spec.runtime.cost_model_options)
            self.cost_model = self.population.cost_model
        else:
            from repro.api.costmodel import get_cost_model

            self.cost_model = get_cost_model(
                spec.runtime.cost_model or "constant",
                spec.runtime.cost_model_options)
        self.coord = MMFLCoordinator(
            task_names=self.names,
            n_clients=spec.clients.n_clients,
            alpha=spec.allocation.alpha,
            strategy=ALLOCATORS.get(spec.allocation.strategy),
            participation=spec.clients.participation,
            seed=spec.seed,
            eligibility=eligibility,
            policy=policy_from_spec(spec.policy, spec.allocation.strategy),
        )
        if self.population is not None:
            self.coord.eligibility = self.population.set_eligibility(
                self.coord.eligibility)
        self.incentive = incentive

    def _set_eligibility(self, elig) -> np.ndarray:
        """Adopt a (K, S) eligibility matrix, mirroring it into the
        population's struct-of-arrays when one is configured."""
        elig = np.asarray(elig, bool)
        if self.population is not None:
            return self.population.set_eligibility(elig)
        return elig

    def _acc_of(self, name: str) -> float:
        """Current next-token eval accuracy of one task's global params."""
        return spans.fetch(self._eval_acc[name](self.tasks[name]["params"]))

    def _run_task_round(self, name: str, ids, rng, want_norm: bool = False):
        """One task's round: cohort execution + aggregation through the
        pluggable backend. Returns (reported loss, mean cohort update norm
        or None — computed only when the allocation policy opts in)."""
        import jax
        import jax.numpy as jnp

        from repro.launch.train import assemble_batch

        t = self.tasks[name]
        with spans.span("assemble"):
            w = self.coord.client_weights(ids)
            batch = assemble_batch(t, self.data[name], ids, w, rng)
            if t["tau"] <= 1:
                # fused server step as a SINGLE-unit cohort (state =
                # params+opt; the p_k weighting lives inside the batch's
                # client_weights)
                job = ClientBatch(ids[:1], None, (jax.tree.map(lambda v: v[None], batch),))
                state = CohortTask(name, (t["params"], t["opt"]), t["opt_local_fn"])
            else:
                # TRUE FedAvg: one cohort row per batch row (clients tiled
                # to the task batch size, as assemble_batch lays them out)
                w_rows = batch["client_weights"]
                rows = {k: v[:, None] for k, v in batch.items() if k != "client_weights"}
                reps = int(np.ceil(len(w_rows) / max(len(ids), 1)))
                row_ids = np.tile(np.asarray(ids), reps)[: len(w_rows)]
                job = ClientBatch(row_ids, None, (rows,))
                state = CohortTask(name, t["params"], t["local_fn"])
        with spans.span("cohort"):
            res = self.backend.run_cohort(state, job)
        norm = None
        if t["tau"] <= 1:
            if want_norm:
                # displacement of the params (not opt-state) from the step,
                # read before the fold donates the row
                norm = spans.fetch(stacked_delta_norms(res.updates[0], t["params"])[0])
            with spans.span("fold"):
                # the new state is the row's own buffers; the old state is
                # freed here, by the rebinding, as nothing donated it
                t["params"], t["opt"] = take_row(res.updates)
            return spans.fetch(res.losses[0]), norm
        if want_norm:
            norm = spans.fetch(stacked_delta_norms(res.updates, t["params"]).mean())
        # pluggable server fold ("fedavg" = the direct backend weighted
        # mean over absolute cohort params, the bit-exact legacy trace)
        with spans.span("fold"):
            t["params"], self._server_state[name] = self.aggregator.aggregate_params(
                t["params"], res.updates, w_rows, self._server_state[name],
                normalizer=jnp.maximum(w_rows.sum(), 1e-9)
            )
        return spans.fetch(res.losses.mean()), norm

    def _resume(self, hit, rng) -> List[dict]:
        """Restore a ``CheckpointManager.begin`` hit in place (task
        pytrees, coordinator, data RNG, incentive, cost model) and return
        its replayed round records, so the RunResult covers the WHOLE
        run, not just the post-resume tail."""
        import jax
        import jax.numpy as jnp

        state = hit.coordinator
        # raises on aggregator/options mismatch — the saved server moments
        # would be silently reinterpreted
        self.aggregator.load_state(state["aggregator"])
        for a in self.names:
            saved = hit.tasks[a]
            self.tasks[a]["params"] = jax.tree.map(jnp.asarray, saved["params"])
            self.tasks[a]["opt"] = jax.tree.map(jnp.asarray, saved["opt"])
            srv = saved.get("server_state")
            if srv is not None:
                self._server_state[a] = jax.tree.map(jnp.asarray, srv)
        self.coord.load_state(state["coordinator"])
        rng.bit_generator.state = state["data_rng"]
        if "population" in state and self.population is not None:
            self.population.validate_config(state["population"])
        # incentive ledger + re-auctioned eligibility, so resumed
        # recruitment is budget- and schedule-exact
        if self.incentive is not None and "incentive" in state:
            self.incentive.load_state(state["incentive"])
            if self.incentive.eligibility is not None:
                self.coord.eligibility = self._set_eligibility(
                    self.incentive.eligibility)
        self.cost_model.load_state(state["cost_model"])
        return [rec for rec in hit.history if rec["kind"] == "round"]

    def _save(self, ckpt, step: int, rng) -> None:
        """One checkpoint step: the model and optimizer pytrees plus the
        bounded JSON control state. The round curves are not in it: they
        live in the sidecar, so the payload is O(1) in run length."""
        task_state = {}
        for a in self.names:
            task_state[a] = {
                "params": self.tasks[a]["params"],
                "opt": self.tasks[a]["opt"],
            }
            # optimizer moments of a stateful aggregator ride with the
            # model pytrees; omitted for stateless rules
            if self._server_state[a] is not None:
                task_state[a]["server_state"] = self._server_state[a]
        coord_payload = {
            "coordinator": self.coord.state_dict(),
            "data_rng": rng.bit_generator.state,
            "aggregator": self.aggregator.state_dict(),
            "cost_model": self.cost_model.state_dict(),
        }
        if self.population is not None:
            coord_payload["population"] = self.population.config_record()
        if self.incentive is not None:
            coord_payload["incentive"] = self.incentive.state_dict()
        ckpt.save(step, task_state, coordinator_state=coord_payload,
                  engine_kind="sync")

    def run(self, verbose: bool = False) -> RunResult:
        spec, rt = self.spec, self.spec.runtime
        rng = np.random.default_rng(spec.seed)
        # the cost model samples from its OWN stream (seed + 3), sized
        # by the per-task parameter counts (FLOP scaling input)
        import jax as _jax

        self.cost_model.reset(
            spec.clients.n_clients, len(self.names),
            np.random.default_rng(spec.seed + 3),
            task_sizes=[float(sum(np.size(leaf) for leaf in
                                  _jax.tree.leaves(self.tasks[a]["params"])))
                        for a in self.names])

        # one record per round: the sidecar's round record, kept in memory
        # for the RunResult
        rounds: List[dict] = []
        ckpt, start_round = None, 0
        if rt.checkpoint_dir:
            from repro.checkpoint import CheckpointManager

            ckpt = CheckpointManager(rt.checkpoint_dir,
                                     keep=rt.checkpoint_keep)
            # shared resume preamble (CheckpointManager.begin): resume
            # gate, foreign-engine guard, sidecar truncation + replay,
            # stale-step clear
            hit = ckpt.begin("sync", rt.resume)
            if hit is not None:
                rounds = self._resume(hit, rng)
                start_round = hit.step
                if verbose:
                    print(f"resumed from round {hit.step}")
        want_norms = self.coord.wants_update_norms
        clock = rounds[-1]["wall_clock"] if rounds else 0.0
        for r in range(start_round, rt.rounds):
            with spans.step("round", r):
                if self.incentive is not None:
                    upd = self.incentive.recruit(
                        RoundContext(
                            round=r,
                            task_names=self.names,
                            losses=self.coord.losses,
                            alpha=spec.allocation.alpha,
                            n_clients=spec.clients.n_clients,
                            eligibility=self.coord.eligibility,
                        )
                    )
                    if upd is not None:
                        self.coord.eligibility = self._set_eligibility(upd.eligibility)
                alloc = self.coord.next_round()
                t0 = time.time()
                line = []
                row = np.full(spec.clients.n_clients, -1, np.int64)
                norms = np.full(len(self.names), np.nan) if want_norms else None
                # simulated round duration: the lockstep barrier waits for
                # the slowest sampled (client, task) latency this round
                round_time = 0.0
                for s, a in enumerate(self.names):
                    ids = alloc[a]
                    if len(ids) == 0:
                        line.append(f"{a}: -")
                        continue
                    row[ids] = s
                    if self.population is not None:
                        # cohort-batched latency sampling (same stream order)
                        totals, _ = self.population.sample_latencies(
                            ids, s, 1.0, times=clock)
                        round_time = max(round_time, float(totals.max()))
                    else:
                        for i in ids:
                            round_time = max(
                                round_time,
                                self.cost_model.sample_latency(
                                    int(i), s, 1.0, time=clock).total)
                    loss, norm = self._run_task_round(a, ids, rng, want_norms)
                    if want_norms and norm is not None:
                        norms[s] = norm
                    self.coord.report(a, loss)
                    line.append(f"{a}: {loss:.3f} ({len(ids)}c)")
                self.coord.observe([len(alloc[a]) for a in self.names], norms)
                with spans.span("eval"):
                    acc = [self._acc_of(a) for a in self.names]
                clock += round_time
                rounds.append({
                    "kind": "round",
                    "loss": [self.coord.tasks[a].loss for a in self.names],
                    "counts": [len(alloc[a]) for a in self.names],
                    "alloc": row.tolist(),
                    "acc": acc,
                    "wall_clock": float(clock),
                })
                if ckpt is not None:
                    # whole-run history streams into the append-only sidecar
                    # (buffered; the next save fsyncs + commits the offset)
                    ckpt.append_history(rounds[-1])
                if verbose:
                    print(f"round {r + 1:3d} [{time.time() - t0:5.1f}s] " + " | ".join(line))
                if ckpt and (r + 1) % rt.checkpoint_every == 0:
                    self._save(ckpt, r + 1, rng)

        if ckpt is not None:
            ckpt.close()
        n_tasks = len(self.names)
        counts = np.array([rec["counts"] for rec in rounds],
                          np.int64).reshape(-1, n_tasks)
        return RunResult(
            scenario=spec.name,
            mode="sync",
            task_names=self.names,
            loss=np.array([rec["loss"] for rec in rounds]),
            acc=np.array([rec["acc"] for rec in rounds]).reshape(-1, n_tasks),
            arrivals=counts.sum(axis=0),
            alloc_counts=counts,
            alloc=np.array([rec["alloc"] for rec in rounds]),
            wall_clock_sim=np.array([rec["wall_clock"] for rec in rounds],
                                    np.float64),
            spec=spec,
            params=[self.tasks[a]["params"] for a in self.names],
        )


# ------------------------------------------------------------ entry point


def _require_named_options(spec: ScenarioSpec) -> None:
    """One options-without-name check for every optional runtime axis
    (previously duplicated ad hoc per axis): options only make sense
    once an entry is named — silently ignoring them would hide typos."""
    rt = spec.runtime
    axes = [
        ("runtime", "aggregator", rt.aggregator, rt.aggregator_options,
         "fedadam"),
        ("runtime", "buffer_controller", rt.buffer_controller,
         rt.buffer_controller_options, "staleness_target"),
        ("runtime", "cost_model", rt.cost_model, rt.cost_model_options,
         "device_tiers"),
        ("clients", "population", spec.clients.population,
         spec.clients.population_options, "vectorized"),
    ]
    for scope, axis, name, options, example in axes:
        if name is None and options:
            article = "an" if axis[0] in "aeiou" else "a"
            raise ValueError(
                f"{scope}.{axis}_options were given without {article} "
                f"{axis}; name one (e.g. {example!r}) or drop the "
                "options")


def run_scenario(spec: ScenarioSpec, verbose: bool = False) -> RunResult:
    """Build and run the scenario described by ``spec``.

    Resolves every registry key up front (so typos fail fast with the
    valid names), runs the optional recruitment auction to produce the
    eligibility matrix, then drives the sync or async runtime behind the
    shared Engine protocol.
    """
    # snapshot: the RunResult's provenance record must not change if the
    # caller mutates the spec after the run (e.g. to rerun in async mode)
    spec = copy.deepcopy(spec)
    family = TASK_FAMILIES.get(spec.family)()
    ALLOCATORS.get(spec.allocation.strategy)
    if spec.policy is not None:
        POLICIES.get(spec.policy.name)
    ARRIVAL_PROCESSES.get(spec.clients.arrival_process)
    BACKENDS.get(spec.runtime.backend)
    if spec.runtime.buffer_controller is not None:
        BUFFER_CONTROLLERS.get(spec.runtime.buffer_controller)
        if spec.runtime.mode == "sync":
            raise ValueError(
                f"buffer_controller "
                f"{spec.runtime.buffer_controller!r} only applies to "
                "mode='async' (sync rounds have no arrival buffers); "
                "drop it or switch the runtime mode"
            )
    if spec.runtime.aggregator is not None:
        AGGREGATORS.get(spec.runtime.aggregator)
    if spec.runtime.cost_model is not None:
        COST_MODELS.get(spec.runtime.cost_model)
    if spec.clients.population is not None:
        POPULATIONS.get(spec.clients.population)
    _require_named_options(spec)
    auction_summary = None
    eligibility = None
    incentive = None
    if spec.auction is not None:
        if spec.auction.budget <= 0:
            raise ValueError(
                f"auction.budget must be positive, got {spec.auction.budget}: "
                "a non-positive budget recruits no clients (all-False "
                "eligibility matrix), so no task could ever train"
            )
        INCENTIVES.get(spec.auction.incentive)
        K, S = spec.clients.n_clients, len(spec.tasks)
        incentive = incentive_from_spec(spec.auction, K, S)
        # prime round 0; a mechanism may legally defer (return None), in
        # which case everyone stays eligible until it first auctions
        upd = incentive.recruit(
            RoundContext(round=0, task_names=[t.name for t in spec.tasks], n_clients=K)
        )
        auction_summary = {
            "mechanism": spec.auction.mechanism,
            "budget": spec.auction.budget,
        }
        if upd is not None:
            eligibility = upd.eligibility
            res = upd.result
            if res is not None:
                auction_summary.update(
                    {
                        "take_up": res.take_up.tolist(),
                        "min_take_up": res.min_take_up,
                        "diff_take_up": res.diff_take_up,
                        "spent": float(res.spent),
                    }
                )

    if spec.runtime.mode == "sync":
        engine = family.sync_engine(spec, eligibility, incentive)
    else:
        engine = family.async_engine(spec, eligibility, incentive)

    t0 = time.time()
    result = engine.run(verbose=verbose)
    result.wall_time = time.time() - t0
    if incentive is not None:
        # cross-round ledger: what the per-round protocol actually spent
        auction_summary["incentive"] = spec.auction.incentive
        auction_summary["auctions_run"] = int(incentive.auctions)
        auction_summary["total_spent"] = float(incentive.spent)
    result.auction = auction_summary
    return result
