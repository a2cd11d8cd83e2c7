"""Async MMFL engine: staleness weighting, buffered-aggregation
sync-equivalence, on-the-fly fair allocation, heterogeneity profiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.allocation import (AllocationStrategy, assign_completion,
                                   alpha_fair_probs)
from repro.core.mmfl import MMFLCoordinator
from repro.fed import (AsyncConfig, AsyncMMFLEngine, MMFLTrainer,
                       TrainConfig, client_speeds, standard_tasks)
from repro.fed.server import aggregate, aggregate_stale, staleness_weights
from repro.fed.trainer import (cohort_update, init_task_models,
                               task_round_key)


@pytest.fixture(scope="module")
def two_tasks():
    return standard_tasks(["synth-mnist", "synth-fmnist"], n_clients=16,
                          seed=0, n_range=(50, 80))


# ---------------------------------------------------------------- staleness

def test_staleness_weights_decay():
    w = np.ones(4, np.float32)
    s = np.array([0.0, 1.0, 2.0, 5.0])
    out = np.asarray(staleness_weights(w, s, beta=0.7))
    assert np.isclose(out[0], 1.0)              # fresh update undiscounted
    assert np.all(np.diff(out) < 0)             # monotone decay
    np.testing.assert_allclose(out, (1.0 + s) ** -0.7, rtol=1e-6)


def test_staleness_beta_zero_is_plain_fedavg():
    w = np.array([0.2, 0.5, 0.3], np.float32)
    s = np.array([0.0, 3.0, 9.0])
    np.testing.assert_allclose(np.asarray(staleness_weights(w, s, 0.0)), w)


def test_aggregate_stale_matches_manual():
    """Discounted deltas normalised by the UNDISCOUNTED weight sum."""
    cohort = jnp.arange(12.0).reshape(3, 4)
    w = np.array([1.0, 1.0, 1.0], np.float32)
    s = np.array([0.0, 1.0, 3.0])
    beta = 1.0
    eff = w / (1.0 + s)
    expect = (eff[:, None] * np.asarray(cohort)).sum(0) / w.sum()
    got = np.asarray(aggregate_stale(cohort, w, s, beta))
    np.testing.assert_allclose(got, expect, rtol=1e-6)


def test_aggregate_stale_uniform_staleness_damps_step():
    """A uniformly stale buffer must take a SMALLER step, not have the
    discount cancel in renormalisation."""
    cohort = jnp.ones((4, 3))
    w = np.ones(4, np.float32)
    fresh = np.asarray(aggregate_stale(cohort, w, np.zeros(4), 0.5))
    stale = np.asarray(aggregate_stale(cohort, w, np.full(4, 3.0), 0.5))
    np.testing.assert_allclose(fresh, 1.0, rtol=1e-6)
    np.testing.assert_allclose(stale, (1.0 + 3.0) ** -0.5, rtol=1e-6)


# -------------------------------------------------- sync equivalence (B=K)

def test_equal_speeds_full_buffer_equals_sync_round1():
    """Acceptance: equal client speeds + buffer_size == cohort size ==>
    the async engine's first aggregation reproduces the sync trainer's
    round-1 params to 1e-6 (single task, full participation)."""
    K = 10
    tasks = standard_tasks(["synth-mnist"], n_clients=K, seed=0,
                           n_range=(40, 60))
    p0 = init_task_models(tasks, jax.random.PRNGKey(0), 64, 2)[0]
    cohort = cohort_update(p0, task_round_key(0, 0, 0), tasks[0],
                           np.arange(K), 3, 0.1, 32)
    sync_p = aggregate(cohort, jnp.asarray(tasks[0].p_k))

    cfg = AsyncConfig(total_arrivals=K, buffer_size=K, tau=3, seed=0,
                      speed_profile="uniform")
    eng = AsyncMMFLEngine.from_fed_tasks(tasks, cfg)
    h = eng.run()
    assert h.versions.tolist() == [1]
    for a, b in zip(jax.tree_util.tree_leaves(sync_p),
                    jax.tree_util.tree_leaves(eng._params[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_disjoint_eligibility_sync_equivalence(two_tasks):
    """Two tasks, each client eligible for exactly one: allocation is
    forced in both drivers, so async-with-full-buffers == sync round 1."""
    K = two_tasks[0].n_clients
    elig = np.zeros((K, 2), bool)
    elig[: K // 2, 0] = True
    elig[K // 2:, 1] = True
    cfg = TrainConfig(rounds=1, participation=1.0, tau=2, seed=0)
    MMFLTrainer(two_tasks, cfg, eligibility=elig).run()

    p0 = init_task_models(two_tasks, jax.random.PRNGKey(0), 64, 2,
                          ("synth-cifar",), 3)
    expect = []
    for s, ids in ((0, np.arange(K // 2)), (1, np.arange(K // 2, K))):
        cohort = cohort_update(p0[s], task_round_key(0, s, 0),
                               two_tasks[s], ids, 2, 0.1, 32)
        expect.append(aggregate(cohort,
                                jnp.asarray(two_tasks[s].p_k[ids])))

    acfg = AsyncConfig(total_arrivals=K, buffer_size=K // 2, tau=2,
                       seed=0, speed_profile="uniform")
    eng = AsyncMMFLEngine.from_fed_tasks(two_tasks, acfg,
                                         eligibility=elig)
    eng.run()
    for s in range(2):
        for a, b in zip(jax.tree_util.tree_leaves(expect[s]),
                        jax.tree_util.tree_leaves(eng._params[s])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


# ------------------------------------------------------------- fairness

def test_async_fairness_spread_not_worse_than_random(two_tasks):
    """Fair-async mode: alpha-fair on-the-fly allocation keeps the spread
    across task accuracies no worse than random allocation, and the min
    accuracy at least as good (seeded, tiny config tolerances)."""
    res = {}
    for name, strat in (("fedfair", AllocationStrategy.FEDFAIR),
                        ("random", AllocationStrategy.RANDOM)):
        var_tail, min_tail = [], []
        for seed in (0, 1):
            cfg = AsyncConfig(total_arrivals=160, buffer_size=4, tau=3,
                              seed=seed, strategy=strat,
                              speed_profile="bimodal")
            h = AsyncMMFLEngine.from_fed_tasks(two_tasks, cfg).run()
            var_tail.append(h.var_acc[-5:].mean())
            min_tail.append(h.min_acc[-5:].mean())
        res[name] = (np.mean(var_tail), np.mean(min_tail))
    assert res["fedfair"][0] <= res["random"][0] + 1e-3
    assert res["fedfair"][1] >= res["random"][1] - 0.02


def test_fedfair_async_sends_more_arrivals_to_hard_task(two_tasks):
    cfg = AsyncConfig(total_arrivals=200, buffer_size=4, tau=3, seed=0)
    h = AsyncMMFLEngine.from_fed_tasks(two_tasks, cfg).run()
    # synth-fmnist (task 1) is persistently harder -> more completions
    assert h.arrivals[1] > h.arrivals[0]


# ----------------------------------------------- heterogeneity & staleness

def test_bimodal_speeds_fast_clients_contribute_more(two_tasks):
    cfg = AsyncConfig(total_arrivals=160, buffer_size=4, tau=2, seed=0,
                      speed_profile="bimodal", speed_spread=4.0)
    eng = AsyncMMFLEngine.from_fed_tasks(two_tasks, cfg)
    h = eng.run()
    fast = eng.speeds == 1.0
    slow = ~fast
    assert fast.any() and slow.any()
    assert (h.updates_per_client[fast].mean()
            > 2.0 * h.updates_per_client[slow].mean())
    assert h.staleness_mean.max() > 0        # buffers really go stale


def test_speed_profiles():
    rng = np.random.default_rng(0)
    assert np.all(client_speeds("uniform", 10, rng) == 1.0)
    bi = client_speeds("bimodal", 200, rng, spread=4.0, slow_fraction=0.5)
    assert set(np.round(bi, 6)) == {0.25, 1.0}
    ln = client_speeds("lognormal", 200, rng, spread=4.0)
    assert np.all(ln > 0) and ln.std() > 0
    with pytest.raises(ValueError):
        client_speeds("warp", 4, rng)


def test_max_staleness_drops_updates(two_tasks):
    cfg = AsyncConfig(total_arrivals=200, buffer_size=4, tau=2, seed=0,
                      speed_profile="bimodal", speed_spread=8.0,
                      max_staleness=0)
    h = AsyncMMFLEngine.from_fed_tasks(two_tasks, cfg).run()
    assert h.dropped > 0                     # stale work discarded
    assert len(h.time) > 0                   # ...but training continued
    assert h.min_acc[-1] > 0.2


# ----------------------------------------------- on-the-fly allocation

def test_async_eligibility_respected(two_tasks):
    K = two_tasks[0].n_clients
    elig = np.zeros((K, 2), bool)
    elig[: K // 2, 0] = True
    elig[K // 2:, 1] = True
    elig[0] = False                          # client 0 recruited nowhere
    cfg = AsyncConfig(total_arrivals=80, buffer_size=3, tau=2, seed=0)
    eng = AsyncMMFLEngine.from_fed_tasks(two_tasks, cfg, eligibility=elig)
    h = eng.run()
    assert all(elig[c, s] for c, s in h.assignments)
    assert h.updates_per_client[0] == 0


def test_coordinator_assign_next_prefers_worst_task():
    c = MMFLCoordinator(["easy", "hard"], n_clients=10, alpha=8.0, seed=0)
    c.report("easy", 0.1)
    c.report("hard", 0.9)
    picks = np.array([c.assign_next(i % 10) for i in range(200)])
    assert (picks == 1).mean() > 0.9


def test_coordinator_assign_next_round_robin_cycles():
    c = MMFLCoordinator(["a", "b", "c"], n_clients=6, seed=0,
                        strategy=AllocationStrategy.ROUND_ROBIN)
    picks = [c.assign_next(0) for _ in range(6)]
    assert picks == [0, 1, 2, 0, 1, 2]


def test_assign_completion_jit_and_eligibility():
    losses = jnp.array([0.5, 0.5, 0.5])
    elig = jnp.array([0.0, 1.0, 0.0])
    f = jax.jit(assign_completion)
    picks = {int(f(jax.random.PRNGKey(i), losses, elig, 3.0))
             for i in range(20)}
    assert picks == {1}
    # eligible for nothing -> -1 sentinel, never an ineligible task
    assert int(f(jax.random.PRNGKey(0), losses, jnp.zeros(3), 3.0)) == -1
    # matches Eq. 4 restricted+renormalised when all eligible
    p = np.asarray(alpha_fair_probs(jnp.array([0.2, 0.8]), 3.0))
    counts = np.zeros(2)
    for i in range(400):
        counts[int(assign_completion(jax.random.PRNGKey(i),
                                     jnp.array([0.2, 0.8]),
                                     jnp.ones(2), 3.0))] += 1
    np.testing.assert_allclose(counts / counts.sum(), p, atol=0.08)


# ------------------------------------- the flush's compiled deltas and step

ROW_SHAPES = {"a": ((7,), jnp.float32), "b": ((3, 5), jnp.bfloat16),
              "c": ((2, 3, 6), jnp.float32)}


def _tree(rng, lead=(), shapes=ROW_SHAPES):
    return {name: jnp.asarray(rng.standard_normal(lead + shape), dtype)
            for name, (shape, dtype) in shapes.items()}


def _same_bits(x, y):
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y), strict=True):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def _groups(split, seed=0, shapes=ROW_SHAPES):
    """Version groups of a flush: per group its clients (a permutation
    of 0..k-1 split in order), cohort rows and base."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(sum(split))
    out, at = [], 0
    for n in split:
        out.append((ids[at:at + n], _tree(rng, (n,), shapes), _tree(rng, (), shapes)))
        at += n
    return out


def _write_all(groups):
    """The engine's loop: the rows of each group in version order, its
    clients' weights appended in the same order."""
    from repro.fed.async_engine import write_deltas

    k = sum(len(ids) for ids, _, _ in groups)
    stacked, weights = None, []
    for ids, cohort, base in groups:
        stacked = write_deltas(stacked, cohort, base, len(weights), k)
        weights.extend(ids.tolist())
    return stacked, weights


def _eager_stack(groups):
    """The eager flush: one ``c[i] - b`` tree a client, then the stack."""
    deltas = [jax.tree.map(lambda c, b: c[i] - b, cohort, base)
              for ids, cohort, base in groups for i in range(len(ids))]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *deltas)


@pytest.mark.parametrize("split", [(4,), (3, 1), (1, 2, 1), (1, 1, 1, 1)])
def test_write_deltas_equals_the_eager_stack(split):
    groups = _groups(split)
    stacked, weights = _write_all(groups)
    _same_bits(stacked, _eager_stack(groups))
    # row r is the delta of the client whose weight is r-th
    where = {int(c): (cohort, base, i) for ids, cohort, base in groups
             for i, c in enumerate(ids)}
    for r, client in enumerate(weights):
        cohort, base, i = where[client]
        _same_bits(jax.tree.map(lambda s: s[r], stacked),
                   jax.tree.map(lambda c, b: c[i] - b, cohort, base))


def _compositions(k):
    if k == 0:
        return [()]
    return [(n,) + rest for n in range(1, k + 1) for rest in _compositions(k - n)]


class _Compiles:
    """Backend compiles while the block runs."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def _event(self, event, duration, **_):
        self.n += event == "/jax/core/compile/backend_compile_duration"

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)


def test_write_deltas_compiles_at_most_the_square_of_the_buffer():
    """Every split of every k <= 4 kept jobs: the first flush at a new k
    compiles at most 2k - 1 programs, 16 over k <= 4, and the other
    splits of that k compile none, however the flushes split."""
    buffer = 4
    # a tree of its own: no other test has compiled its programs
    shapes = {name: ((s[0] + 4,) + s[1:], dtype) for name, (s, dtype) in ROW_SHAPES.items()}
    splits = {k: [_groups(s, seed=k, shapes=shapes) for s in _compositions(k)]
              for k in range(1, buffer + 1)}
    assert len(splits[buffer]) == 8
    first, rest = {}, {}
    for k, flushes in splits.items():
        with _Compiles() as c:
            jax.block_until_ready(_write_all(flushes[0])[0])
        first[k] = c.n
        with _Compiles() as c:
            for groups in flushes[1:]:
                jax.block_until_ready(_write_all(groups)[0])
        rest[k] = c.n
    assert all(0 < n <= 2 * k - 1 for k, n in first.items()), first
    assert sum(first.values()) <= buffer ** 2
    assert not any(rest.values()), rest


def test_server_step_at_rate_one_equals_the_eager_step():
    from repro.fed.async_engine import _server_step

    rng = np.random.default_rng(1)
    params, update = _tree(rng), _tree(rng)
    _same_bits(_server_step(params, update, 1.0),
               jax.tree.map(lambda p, d: p + 1.0 * d, params, update))


def _eager_write_deltas(stack, cohort, base, row, k):
    """The pre-compiled flush's deltas, group by group: the rows so far,
    then one eager ``c[i] - b`` a client, stacked (k rows at the last)."""
    del k
    rows = [jax.tree.map(lambda s: s[r], stack) for r in range(row)]
    n = jax.tree.leaves(cohort)[0].shape[0]
    rows += [jax.tree.map(lambda c, b: c[i] - b, cohort, base) for i in range(n)]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *rows)


def _run_recorded(tasks, aggregator, eager, monkeypatch):
    """A bimodal two-task run at server_lr 1.0 with the update-norm
    policy; its history, final state and the norms the policy saw."""
    from repro.api.policy import GradNormPolicy
    from repro.fed import async_engine

    seen = []

    class Recorded(GradNormPolicy):
        def observe(self, obs):
            seen.append(np.asarray(obs.update_norms))
            super().observe(obs)

    with monkeypatch.context() as m:
        if eager:
            m.setattr(async_engine, "write_deltas", _eager_write_deltas)
            m.setattr(async_engine, "_server_step", lambda p, d, lr: jax.tree.map(
                lambda a, b: a + lr * b, p, d))
        cfg = AsyncConfig(total_arrivals=40, buffer_size=4, tau=2, seed=0,
                          speed_profile="bimodal", policy=Recorded(),
                          aggregator=aggregator,
                          aggregator_options={"fused": False} if aggregator == "fedadam" else {})
        eng = AsyncMMFLEngine.from_fed_tasks(tasks, cfg)
        h = eng.run()
    return h, eng._params, eng._server_state, seen


@pytest.mark.parametrize("aggregator", ["fedavg", "fedadam"])
def test_compiled_flush_history_equals_the_eager_flush(two_tasks, aggregator, monkeypatch):
    from repro.fed import async_engine

    split = []
    real = async_engine.write_deltas

    def spy(stack, cohort, base, row, k):
        split.append(row)
        return real(stack, cohort, base, row, k)

    monkeypatch.setattr(async_engine, "write_deltas", spy)
    h, params, state, norms = _run_recorded(two_tasks, aggregator, False, monkeypatch)
    assert any(row > 0 for row in split)        # some flush wrote a later group
    ref, ref_params, ref_state, ref_norms = _run_recorded(
        two_tasks, aggregator, True, monkeypatch)
    for name in ("time", "task", "metric", "staleness_mean", "versions"):
        assert getattr(h, name).tobytes() == getattr(ref, name).tobytes(), name
    _same_bits(params, ref_params)
    _same_bits(state, ref_state)
    # the update-norm policy reads the same norms off the compiled stack
    assert len(norms) == len(h.time)
    for a, b in zip(norms, ref_norms, strict=True):
        assert a.tobytes() == b.tobytes()


def test_delta_programs_counts_each_flush_that_kept_a_job(two_tasks, tmp_path,
                                                        monkeypatch):
    from repro import spans

    flushes = []
    real = AsyncMMFLEngine._flush

    def counted(self, s, t):
        flushes.append(s)
        return real(self, s, t)

    monkeypatch.setattr(AsyncMMFLEngine, "_flush", counted)
    cfg = AsyncConfig(total_arrivals=60, buffer_size=4, tau=2, seed=0,
                      speed_profile="bimodal", speed_spread=8.0, max_staleness=0)
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        h = AsyncMMFLEngine.from_fed_tasks(two_tasks, cfg).run()
    assert len(flushes) > len(h.time)           # some flushes dropped every job
    assert spans.counters()["delta_programs"] == len(h.time) > 0
