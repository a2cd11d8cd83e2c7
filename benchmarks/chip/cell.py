"""Drive one cell through the system's own entry points.

The engine is built by the ``arch`` task family exactly as
``run_scenario`` builds it, with the seed's weights and token shards put
in place of the ones the system made. Its own round loop then runs: the
harness only wraps, on the instances it built, one call the engine makes
once per round (``coord.next_round``) or once per flush of a task (the
task adapter's ``evaluate``), and the backend's ``run_cohort``. At each
such boundary it takes the time; the window ends at the first boundary
after its length, by raising out of the engine's loop.

Set-up runs the engine until every shape the window uses has compiled
and the configuration's task has taken its first three steps, which are
recorded for the reference.
"""
from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import reference
import traffic as traffic_gen

RECORDED_STEPS = 3


class WindowClosed(Exception):
    """Raised at the first boundary after the window's end."""


def task_name(cfg: dict) -> str:
    return f"bench-{cfg['name']}"


def program_seed(seed: int, tr: dict) -> int:
    """The system's own seed (client speeds, arrivals, allocation draws) as
    a 31-bit int: the traffic file's ``arrivals_seed`` where it fixes the
    arrivals for every run, else the run's seed."""
    return int(tr.get("arrivals_seed", seed)) % (2**31 - 1)


def build_spec(cfg: dict, tr: dict, seed: int, name: str):
    from repro.api import (AllocationSpec, ClientPopulationSpec, RuntimeSpec,
                           ScenarioSpec, TaskSpec)

    def opts(preset, o):
        out = {"preset": preset, "seq": o["seq"], "tau": o["tau"], "local_lr": tr["local_lr"]}
        if "rows" in o:
            out["batch"] = o["rows"]
        return out

    main = {"seq": tr["seq"], "tau": tr["tau"], **({"rows": tr["rows"]} if "rows" in tr else {})}
    tasks = [TaskSpec(task_name(cfg), family="arch", options=opts("full", main)),
             TaskSpec(cfg["partner_tiny"], family="arch", options=opts("tiny", tr["partner"]))]
    alloc = AllocationSpec(strategy=tr["allocation"]["strategy"], alpha=tr["allocation"]["alpha"])
    ps = program_seed(seed, tr)
    if tr["mode"] == "sync":
        return ScenarioSpec(
            name=name, seed=ps, data_seed=ps, tasks=tasks, allocation=alloc,
            clients=ClientPopulationSpec(n_clients=tr["clients"], participation=tr["participation"]),
            runtime=RuntimeSpec(mode="sync", backend=tr["backend"], rounds=10**9, tau=tr["tau"],
                                aggregator=tr["aggregator"],
                                aggregator_options=dict(tr["aggregator_options"])))
    return ScenarioSpec(
        name=name, seed=ps, data_seed=ps, tasks=tasks, allocation=alloc,
        clients=ClientPopulationSpec(n_clients=tr["clients"], speed_profile=tr["speed_profile"]),
        runtime=RuntimeSpec(mode="async", backend=tr["backend"], total_arrivals=10**9,
                            buffer_size=tr["buffer"], beta=tr["beta"], server_lr=tr["server_lr"],
                            tau=tr["tau"], aggregator=tr["aggregator"],
                            aggregator_options=dict(tr["aggregator_options"])))


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(a, b):
    return _leaf_norms(jax.tree.map(lambda x, y: x.astype(jnp.float32) - y, a, b))


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree.flatten_with_path(tree)[0]]


@dataclass
class Recording:
    """What the timed path produced in the configuration's first steps."""
    steps: list = field(default_factory=list)      # per step: list of groups
    grad_norms: np.ndarray | None = None           # from the state after step 1
    change_norms: np.ndarray | None = None         # params after step 3 minus start
    leaves: list = field(default_factory=list)


@dataclass
class Window:
    start: float = 0.0
    end: float = 0.0
    durations: list = field(default_factory=list)  # seconds per round or flush
    tokens: int = 0                                # configuration task, completed
    partner_tokens: int = 0
    failed: int = 0
    compiles: dict = field(default_factory=dict)
    setup_compiles: str = ""


class Cell:
    """One run of one cell: set-up, window, then what the check needs."""

    def __init__(self, cfg: dict, tr: dict, seed: int, name: str, events, fault=None):
        self.cfg, self.tr, self.seed, self.name = cfg, tr, seed, name
        self.events = events                 # compile-event counter (harness)
        self.task = task_name(cfg)
        self.sync = tr["mode"] == "sync"
        self.words = reference.seed_key(seed)
        self.make_weights = reference.weights_fn(cfg)
        self.rec = Recording()
        self.win = Window()
        self.steps = {}                      # task -> completed steps
        self._pending = {}                   # task -> tokens of steps in flight
        self._groups = []                    # configuration task, this flush
        self._version = None
        self._last = None
        self._in_window = False
        self._window_s = 0.0
        self._on_start = None
        self._on_close = None
        self._fault = fault                  # faults.py: a fault planted under the timed path
        self.warmup = []                     # perf_counter of each set-up boundary

    # ------------------------------------------------------------ build

    def build(self):
        """Build the engine as ``run_scenario`` does, then put the seed's
        weights and token shards in place of the system's own."""
        from repro.api.engine import ArchFamily
        from repro.configs.base import register

        mc = reference.family(self.cfg).model_config(self.cfg, self.task)
        register(self.task)(lambda: mc)
        spec = build_spec(self.cfg, self.tr, self.seed, self.name)
        family = ArchFamily()
        tr = self.tr
        self.data = traffic_gen.client_shards(
            self.seed, tr["clients"], tr["shards_per_client"], tr["seq"], self.cfg["vocab_size"])
        if self.sync:
            self.engine = family.sync_engine(spec)
            task = self.engine.tasks[self.task]
            self.engine.data[self.task] = self.data
            self.backend = self.engine.backend
        else:
            self.runner = family.async_engine(spec)
            self.engine = self.runner.engine
            self.adapters = {a.name: a for a in self.engine.tasks}
            task = self.adapters[self.task].task
            self.adapters[self.task].data = self.data
            self.backend = self.engine.backend
        weights = self.make_weights(self.words)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), task["params"])
        want = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
        if got != want:
            raise RuntimeError(f"the system's parameter layout differs from the benchmark's: "
                               f"{got} vs {want}")
        task["params"] = weights
        self.rec.leaves = leaf_names(weights)
        del weights
        self._hook()

    def _hook(self):
        run_cohort = self._run_cohort = self.backend.run_cohort

        def counted_run_cohort(task_state, client_batch, rng=None):
            given = client_batch
            if self._fault is not None:
                task_state, client_batch = self._fault.before(task_state, client_batch)
            res = run_cohort(task_state, client_batch, rng)
            if self._fault is not None:
                res = self._fault.after(task_state, res)
            self._on_cohort(task_state.name, given, res)
            return res

        self.backend.run_cohort = counted_run_cohort
        if self.sync:
            next_round = self.engine.coord.next_round

            def timed_next_round():
                self._boundary()
                return next_round()

            self.engine.coord.next_round = timed_next_round
        else:
            for a in self.engine.tasks:
                self._hook_adapter(a)

    def _hook_adapter(self, a):
        evaluate, client_batch = a.evaluate, a.client_batch

        def timed_evaluate(params):
            value = evaluate(params)
            if self._pending.get(a.name) is not None:
                self._finish_step(a.name, value)
                self._boundary()
            return value

        def versioned_client_batch(seed, version, client_ids):
            self._version = version
            return client_batch(seed, version, client_ids)

        a.evaluate = timed_evaluate
        if a.name == self.task:
            a.client_batch = versioned_client_batch

    # ------------------------------------------------------------ events

    def _tokens(self, client_batch) -> np.ndarray:
        d = client_batch.data[0]
        return d["tokens"] if isinstance(d, dict) else d

    def _on_cohort(self, name, client_batch, res):
        toks = self._tokens(client_batch)
        n = int(np.prod(toks.shape)) * self.tr["tau"]
        self._pending[name] = self._pending.get(name, 0) + n
        if name == self.task and len(self.rec.steps) < RECORDED_STEPS:
            self._groups.append({"version": self._version,
                                 "clients": np.asarray(client_batch.client_ids),
                                 "tokens": np.asarray(toks),
                                 "losses": np.asarray(res.losses, np.float64)})
        if self.sync:
            self._finish_step(name, None)

    def _finish_step(self, name, value):
        n = self._pending.pop(name, 0)
        self.steps[name] = self.steps.get(name, 0) + 1
        if self._in_window:
            if name == self.task:
                self.win.tokens += n
            else:
                self.win.partner_tokens += n
        if name == self.task and self._groups:
            self.rec.steps.append(self._groups)
            self._groups = []
        if value is not None and name == self.task and not math.isfinite(value):
            self.win.failed += self._in_window

    def _boundary(self):
        now = time.perf_counter()
        if self.sync and self._in_window:
            loss = self.engine.coord.tasks[self.task].loss
            self.win.failed += not math.isfinite(loss)
        done = self.steps.get(self.task, 0)
        if done >= 1 and self.rec.grad_norms is None:
            self.rec.grad_norms = self._first_grad_norms()
        if done >= RECORDED_STEPS and self.rec.change_norms is None:
            self.rec.change_norms = np.asarray(_diff_norms(
                self._params(), self.make_weights(self.words)))
        if self._in_window:
            self.win.durations.append(now - self._last)
            if now - self.win.start >= self._window_s:
                self.win.end = now
                self.win.compiles = self.events.since_mark()
                if self._on_close is not None:
                    self._on_close()
                raise WindowClosed
        else:
            self.warmup.append(now)
        if not self._in_window and self._warm():
            self._in_window = True
            self.win.start = now
            self.win.setup_compiles = self.events.summary()
            self.events.mark()
            if self._on_start is not None:
                self._on_start()
            now = time.perf_counter()
            self.win.start = now
        self._last = now

    def _warm(self) -> bool:
        partner = self.cfg["partner_tiny"]
        return (self.steps.get(self.task, 0) >= RECORDED_STEPS and self.steps.get(partner, 0) >= 1
                and self.rec.change_norms is not None)

    def _params(self):
        if self.sync:
            return self.engine.tasks[self.task]["params"]
        return self.engine._params[self._task_index()]

    def _task_index(self) -> int:
        return [a.name for a in self.engine.tasks].index(self.task)

    def _first_grad_norms(self) -> np.ndarray:
        """The first step's gradient as the optimizer got it, from the
        state after that step: the mean client delta for FedAvg, Adam's
        first moment over (1 - b1) for the fused step and FedAdam."""
        if self.sync and self.tr["tau"] > 1:
            return np.asarray(_diff_norms(self._params(), self.make_weights(self.words)))
        if self.sync:
            mu = self.engine.tasks[self.task]["opt"]["mu"]
            return np.asarray(_leaf_norms(mu)) / (1.0 - self.tr["optimizer"]["b1"])
        m = self.engine._server_state[self._task_index()]["m"]
        return np.asarray(_leaf_norms(m)) / (1.0 - self.tr["aggregator_options"]["beta1"])

    # ------------------------------------------------------------ run

    def precompile(self):
        """Async cells: compile the cohort sizes a flush can dispatch
        (1 to the buffer size; the backend pads to powers of two) and the
        per-client delta slices of each, for both tasks, before the
        engine runs. Sync cells compile one cohort per task in the
        engine's own first rounds."""
        if self.sync:
            return
        from repro.api.backend import CohortTask

        seed = self.engine.cfg.seed
        for a in self.engine.tasks:
            base = a.task["params"]
            for n in range(1, self.tr["buffer"] + 1):
                batch = a.client_batch(seed, 0, np.arange(n))
                cohort = self._run_cohort(CohortTask(a.name, base, a.local_fn), batch).updates
                for i in range(n):
                    jax.block_until_ready(jax.tree.map(lambda c, b: c[i] - b, cohort, base))
        self._pending.clear()
        self._groups = []

    def run(self, seconds: float, on_start=None, on_close=None):
        """Set up, then time ``seconds`` of rounds or flushes (0: stop at
        the end of set-up)."""
        self._window_s = seconds
        self._on_start, self._on_close = on_start, on_close
        try:
            (self.engine if self.sync else self.runner).run()
        except WindowClosed:
            pass
        else:
            raise RuntimeError("the engine stopped before the window closed")

    def free(self):
        """Drop every device buffer the engine holds."""
        for attr in ("engine", "runner", "adapters", "backend"):
            self.__dict__.pop(attr, None)
        gc.collect()
