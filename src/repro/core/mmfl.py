"""MMFL coordinator: the production-scale face of FedFairMMFL.

At datacenter scale the "clients" are data silos whose shards map onto the
mesh's data axis, and each MMFL "task" is one of the registered
architectures with its own sharded train_step. The coordinator holds the
per-task prevailing loss and is a thin stateful shell around a pluggable
``AllocationPolicy`` (``repro.api.policy``): the policy produces the
per-round per-task probabilities (Eq. 4 for the default alpha-fair
wrapper) and receives per-round feedback via ``observe``; the coordinator
owns the RNG stream, the eligibility matrix, and the sampling — so legacy
strategies stay bit-exact and stateful policies (bandits, gradient-norm
sampling) plug in without touching the engines.

Everything the coordinator computes is O(S + K) scalars per round — it
never touches tensors, so it composes with any sharded runtime.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.api.policy import (AllocationPolicy, LegacyStrategyPolicy,
                              RoundContext, RoundObservation)
from repro.core.allocation import AllocationStrategy


@dataclass
class TaskState:
    name: str
    loss: float = float("inf")
    rounds_trained: int = 0
    clients_last_round: int = 0


@dataclass
class MMFLCoordinator:
    task_names: List[str]
    n_clients: int
    alpha: float = 3.0
    strategy: AllocationStrategy = AllocationStrategy.FEDFAIR
    participation: float = 1.0
    seed: int = 0
    eligibility: Optional[np.ndarray] = None      # (K, S) auction outcome
    _round: int = 0
    _async_rr: int = 0
    tasks: Dict[str, TaskState] = field(default_factory=dict)
    # stateful allocation policy; None wraps `strategy` bit-exactly
    policy: Optional[AllocationPolicy] = None
    _obs_count: int = 0

    def __post_init__(self):
        self.tasks = {n: TaskState(n) for n in self.task_names}
        self._rng = np.random.default_rng(self.seed)
        if self.eligibility is None:
            self.eligibility = np.ones(
                (self.n_clients, len(self.task_names)), bool)
        if self.policy is None:
            self.policy = LegacyStrategyPolicy(self.strategy)

    @property
    def losses(self) -> np.ndarray:
        return np.array([max(self.tasks[n].loss, 1e-6)
                         for n in self.task_names])

    @property
    def wants_update_norms(self) -> bool:
        """Engines compute per-task cohort update norms only when the
        policy opts in (zero overhead on the legacy wrappers)."""
        return bool(getattr(self.policy, "wants_update_norms", False))

    def report(self, task: str, loss: float):
        self.tasks[task].loss = float(loss)
        self.tasks[task].rounds_trained += 1

    def observe(self, alloc_counts, update_norms=None, task=None):
        """Forward one round's (sync) or one flush's (async) feedback to
        the policy. Never consumes the coordinator RNG stream."""
        self.policy.observe(RoundObservation(
            round=self._obs_count,
            task_names=list(self.task_names),
            losses=self.losses,
            alloc_counts=np.asarray(alloc_counts, np.int64),
            update_norms=(None if update_norms is None
                          else np.asarray(update_norms, np.float64)),
            task=task))
        self._obs_count += 1

    def next_round(self) -> Dict[str, np.ndarray]:
        """Returns task -> array of client ids allocated this round."""
        S = len(self.task_names)
        probs = self._current_probs()
        m = max(1, int(round(self.participation * self.n_clients)))
        active = self._rng.choice(self.n_clients, size=m, replace=False)
        out = {n: [] for n in self.task_names}
        for j, i in enumerate(active):
            elig = self.eligibility[i]
            if not elig.any():
                continue
            if probs is None:                        # round robin
                for off in range(S):
                    s = (self._round + j + off) % S
                    if elig[s]:
                        break
            else:
                pe = probs * elig
                tot = pe.sum()
                if tot <= 0:     # policy zeroed all eligible tasks
                    continue
                s = self._rng.choice(S, p=pe / tot)
            out[self.task_names[s]].append(i)
        self._round += 1
        for n in self.task_names:
            self.tasks[n].clients_last_round = len(out[n])
        return {n: np.array(v, np.int64) for n, v in out.items()}

    def _current_probs(self, client_id=None) -> Optional[np.ndarray]:
        """Per-task allocation probabilities from the policy (None means
        the deterministic round-robin path). Policies never consume the
        coordinator RNG — sampling stays here — so legacy wrappers are
        bit-exact with the pre-policy coordinator."""
        return self.policy.allocate(RoundContext(
            round=self._round,
            task_names=list(self.task_names),
            losses=self.losses,
            alpha=self.alpha,
            n_clients=self.n_clients,
            eligibility=self.eligibility,
            client_id=client_id))

    def assign_next(self, client_id: int) -> Optional[int]:
        """Async (FedAST-style) allocation: a COMPLETING client immediately
        draws its next task from the policy's distribution on prevailing
        losses (Eq. 4 for the default wrapper), restricted to its
        auction-eligible tasks — no round barrier. Returns a task index,
        or None if the client is eligible for nothing (it idles out of
        the pool)."""
        elig = self.eligibility[client_id]
        if not elig.any():
            return None
        S = len(self.task_names)
        probs = self._current_probs(client_id)
        if probs is None:                            # round robin
            # total branch: never falls through to the probabilistic path
            # (probs is None there), even if eligibility is degenerate
            for off in range(S):
                s = (self._async_rr + off) % S
                if elig[s]:
                    self._async_rr = (s + 1) % S
                    return s
            return None
        pe = probs * elig
        tot = pe.sum()
        if tot <= 0:             # policy zeroed all eligible tasks
            return None
        return int(self._rng.choice(S, p=pe / tot))

    def state_dict(self) -> Dict:
        """Full JSON-serializable coordinator state — round counter, RNG
        stream, per-task stats, and the POLICY state — so checkpoint/
        resume reproduces the exact allocation sequence of an
        uninterrupted run, stateful policies included."""
        return {
            "round": self._round,
            "async_rr": self._async_rr,
            "obs_count": self._obs_count,
            "rng_state": self._rng.bit_generator.state,
            "policy": self.policy.state_dict(),
            "tasks": {n: {"loss": t.loss,
                          "rounds_trained": t.rounds_trained,
                          "clients_last_round": t.clients_last_round}
                      for n, t in self.tasks.items()},
        }

    def load_state(self, state: Dict):
        """Inverse of ``state_dict``; refuses a payload without every
        key ``state_dict`` writes, before changing anything."""
        missing = [k for k in ("round", "async_rr", "obs_count",
                               "rng_state", "policy", "tasks")
                   if k not in state]
        if missing:
            raise ValueError(
                f"coordinator payload lacks {missing}: only the layout "
                "state_dict writes is read")
        self._round = int(state["round"])
        self._async_rr = int(state["async_rr"])
        self._obs_count = int(state["obs_count"])
        self._rng.bit_generator.state = state["rng_state"]
        self.policy.load_state(state["policy"])
        for n, ts in state["tasks"].items():
            if n in self.tasks:
                t = self.tasks[n]
                t.loss = float(ts["loss"])
                t.rounds_trained = int(ts["rounds_trained"])
                t.clients_last_round = int(ts["clients_last_round"])

    def client_weights(self, client_ids: np.ndarray,
                       p_k: Optional[np.ndarray] = None) -> np.ndarray:
        """p_{k,Sel} normalised aggregation weights for a batch whose rows
        are the selected clients' shards."""
        if p_k is None:
            p_k = np.ones(self.n_clients) / self.n_clients
        w = p_k[client_ids]
        return (w / max(w.sum(), 1e-12)).astype(np.float32)
