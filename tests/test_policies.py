"""Stateful AllocationPolicy & IncentiveMechanism API.

Covers: legacy-wrapper bit-exactness vs the pre-policy dispatch (sync +
async, alloc traces included), stateful-policy checkpoint resume ==
uninterrupted (arch sync engine) and mid-run state round-trip (async),
per-round re-auction budget accounting, backend-aware buffer sizing,
parallel sweeps, and registry error paths.
"""
import json

import numpy as np
import pytest

from repro.api import (
    INCENTIVES,
    POLICIES,
    AuctionSpec,
    ClientPopulationSpec,
    GradNormPolicy,
    LegacyStrategyPolicy,
    PolicySpec,
    RoundContext,
    RoundObservation,
    RuntimeSpec,
    ScenarioSpec,
    TaskSpec,
    UCBBanditPolicy,
    incentive_from_spec,
    register_policy,
    run_scenario,
)


def two_task_spec(**runtime_kw):
    mode = runtime_kw.pop("mode", "sync")
    return ScenarioSpec(
        name="pol",
        seed=0,
        tasks=[TaskSpec("synth-mnist", options={"n_range": [40, 60]}),
               TaskSpec("synth-fmnist", options={"n_range": [40, 60]})],
        clients=ClientPopulationSpec(n_clients=10, participation=1.0),
        runtime=RuntimeSpec(mode=mode, **runtime_kw))


# ------------------------------------------------- legacy-wrapper parity

@pytest.mark.parametrize("strategy", ["fedfair", "random", "round_robin"])
def test_wrapper_policy_bit_exact_sync(strategy):
    """Acceptance: PolicySpec(<legacy key>) routes through the policy
    dispatch with BIT-identical curves and allocation traces vs the
    implicit allocation.strategy path (which itself matches the PR 3
    traces — tests/test_scenario_api.py pins that)."""
    base = two_task_spec(rounds=4, tau=2)
    base.clients.participation = 0.5
    base.allocation.strategy = strategy
    r_legacy = run_scenario(base)
    wrapped = ScenarioSpec.from_json(base.to_json())
    wrapped.policy = PolicySpec(strategy)
    r_policy = run_scenario(wrapped)
    np.testing.assert_array_equal(r_legacy.acc, r_policy.acc)
    np.testing.assert_array_equal(r_legacy.alloc, r_policy.alloc)
    np.testing.assert_array_equal(r_legacy.alloc_counts,
                                  r_policy.alloc_counts)


@pytest.mark.parametrize("strategy", ["fedfair", "round_robin"])
def test_wrapper_policy_bit_exact_async(strategy):
    base = two_task_spec(mode="async", total_arrivals=30, buffer_size=3,
                         tau=2)
    base.allocation.strategy = strategy
    r_legacy = run_scenario(base)
    wrapped = ScenarioSpec.from_json(base.to_json())
    wrapped.policy = PolicySpec(strategy)
    r_policy = run_scenario(wrapped)
    np.testing.assert_array_equal(r_legacy.loss, r_policy.loss)
    assert r_legacy.assignments == r_policy.assignments


def test_wrapper_policy_bit_exact_with_one_shot_auction():
    """The legacy one-shot auction path through the incentive protocol is
    bit-exact too (same eligibility, same curves)."""
    base = two_task_spec(mode="async", total_arrivals=30, buffer_size=3,
                         tau=2)
    base.auction = AuctionSpec(mechanism="gmmfair", budget=4.0,
                               bid_model="exp4", bid_seed=0)
    r1 = run_scenario(base)
    wrapped = ScenarioSpec.from_json(base.to_json())
    wrapped.policy = PolicySpec("fedfair")
    r2 = run_scenario(wrapped)
    np.testing.assert_array_equal(r1.loss, r2.loss)
    assert r1.assignments == r2.assignments
    assert r1.auction["take_up"] == r2.auction["take_up"]
    assert r1.auction["auctions_run"] == 1


# ----------------------------------------------------- stateful policies

def test_ucb_bandit_explores_every_task_then_exploits():
    pol = UCBBanditPolicy(epsilon=0.2)
    names = ["a", "b", "c"]
    ctx = RoundContext(round=0, task_names=names,
                       losses=np.array([0.5, 0.5, 0.5]))
    first = pol.allocate(ctx)
    assert first.argmax() == 0 and np.isclose(first.sum(), 1.0)
    # feed rounds where task 2 keeps improving fastest
    losses = np.array([0.5, 0.5, 0.5])
    for r in range(6):
        new = losses - np.array([0.001, 0.002, 0.05])
        pol.observe(RoundObservation(round=r, task_names=names,
                                     losses=new,
                                     alloc_counts=np.array([2, 2, 2])))
        losses = new
    probs = pol.allocate(ctx)
    assert probs.argmax() == 2              # biggest loss deltas win
    assert probs.min() >= 0.2 / 3 - 1e-12   # epsilon floor: nobody starves


def test_ucb_bandit_state_roundtrip_mid_run():
    pol = UCBBanditPolicy()
    names = ["a", "b"]
    for r in range(4):
        pol.observe(RoundObservation(
            round=r, task_names=names,
            losses=np.array([0.5 - 0.01 * r, 0.9 - 0.05 * r]),
            alloc_counts=np.array([1, 1])))
    state = json.loads(json.dumps(pol.state_dict()))   # JSON-native
    clone = UCBBanditPolicy()
    clone.load_state(state)
    ctx = RoundContext(round=4, task_names=names,
                       losses=np.array([0.4, 0.6]))
    np.testing.assert_array_equal(pol.allocate(ctx), clone.allocate(ctx))
    assert clone.t == pol.t


def test_grad_norm_policy_follows_observed_norms():
    pol = GradNormPolicy(gamma=1.0, floor=0.0)
    assert pol.wants_update_norms
    names = ["a", "b"]
    ctx = RoundContext(round=0, task_names=names,
                       losses=np.array([0.5, 0.5]))
    np.testing.assert_allclose(pol.allocate(ctx), [0.5, 0.5])  # no obs yet
    pol.observe(RoundObservation(round=0, task_names=names,
                                 losses=np.array([0.5, 0.5]),
                                 alloc_counts=np.array([1, 1]),
                                 update_norms=np.array([1.0, 3.0])))
    np.testing.assert_allclose(pol.allocate(ctx), [0.25, 0.75])
    state = json.loads(json.dumps(pol.state_dict()))
    clone = GradNormPolicy(gamma=1.0, floor=0.0)
    clone.load_state(state)
    np.testing.assert_array_equal(pol.allocate(ctx), clone.allocate(ctx))


def test_stateful_policies_run_end_to_end_sync_and_async():
    for name in ("ucb_bandit", "grad_norm"):
        s = two_task_spec(rounds=3, tau=2)
        s.policy = PolicySpec(name)
        r = run_scenario(s)
        assert r.acc.shape == (3, 2)
        a = two_task_spec(mode="async", total_arrivals=20, buffer_size=4,
                          tau=2)
        a.policy = PolicySpec(name)
        ra = run_scenario(a)
        assert ra.arrivals.sum() == 20


def test_custom_registered_policy_dispatches():
    calls = []

    @register_policy("always_last")
    class AlwaysLast:
        wants_update_norms = False

        def observe(self, obs):
            pass

        def allocate(self, ctx):
            calls.append(True)
            p = np.zeros(len(ctx.task_names))
            p[-1] = 1.0
            return p

        def state_dict(self):
            return {}

        def load_state(self, state):
            pass

    s = two_task_spec(rounds=2, tau=2)
    s.policy = PolicySpec("always_last")
    r = run_scenario(s)
    assert calls
    assert (r.alloc_counts[:, 0] == 0).all()     # everything to last task


# -------------------------------------------------- checkpoint / resume

def arch_spec(tmp, policy=None, auction=None, rounds=6):
    return ScenarioSpec(
        name="arch-resume",
        tasks=[TaskSpec("smollm-135m", family="arch",
                        options={"preset": "tiny", "seq": 16, "batch": 2,
                                 "tau": 1}),
               TaskSpec("qwen3-0.6b", family="arch",
                        options={"preset": "tiny", "seq": 16, "batch": 2,
                                 "tau": 1})],
        clients=ClientPopulationSpec(n_clients=6, participation=0.5),
        policy=policy,
        auction=auction,
        runtime=RuntimeSpec(mode="sync", rounds=rounds, tau=1,
                            checkpoint_dir=tmp, checkpoint_every=3))


def test_resume_stateful_policy_and_periodic_auction_sync(tmp_path):
    """Satellite acceptance: a resumed ucb_bandit + periodic_auction arch
    run produces curves and allocation counts IDENTICAL to the
    uninterrupted run — policy state, incentive ledger, and re-auctioned
    eligibility all thread through the checkpoint."""
    auction = AuctionSpec(mechanism="gmmfair", budget=8.0, bid_seed=0,
                          incentive="periodic_auction",
                          incentive_options={"every": 2})
    policy = PolicySpec("ucb_bandit", {"epsilon": 0.3})
    full = run_scenario(arch_spec(str(tmp_path / "full"), policy, auction))

    half_spec = arch_spec(str(tmp_path / "half"), policy, auction, rounds=3)
    run_scenario(half_spec)                       # checkpoints at round 3
    resumed_spec = arch_spec(str(tmp_path / "half"), policy, auction)
    resumed_spec.runtime.resume = True
    resumed = run_scenario(resumed_spec)

    np.testing.assert_array_equal(full.loss, resumed.loss)
    np.testing.assert_array_equal(full.alloc_counts, resumed.alloc_counts)
    np.testing.assert_array_equal(full.acc, resumed.acc)
    assert full.auction["total_spent"] <= full.auction["budget"] + 1e-9


def test_async_coordinator_policy_state_roundtrip_continues_exactly():
    """Async leg of the resume satellite: serialising the coordinator +
    policy state mid-run into JSON, loading it into a FRESH coordinator,
    and continuing reproduces the uninterrupted assignment stream."""
    from repro.core.mmfl import MMFLCoordinator

    def fresh():
        c = MMFLCoordinator(["a", "b"], n_clients=8, seed=3,
                            policy=UCBBanditPolicy(epsilon=0.25))
        c.report("a", 0.5)
        c.report("b", 0.9)
        return c

    c1 = fresh()
    for r in range(5):
        picks = [c1.assign_next(i) for i in range(8)]
        counts = np.bincount([p for p in picks if p is not None],
                             minlength=2)
        c1.report("a", 0.5 - 0.02 * r)
        c1.report("b", 0.9 - 0.08 * r)
        c1.observe(counts)
    state = json.loads(json.dumps(c1.state_dict()))
    tail1 = [c1.assign_next(i) for i in range(8)]

    c2 = fresh()
    c2.load_state(state)
    tail2 = [c2.assign_next(i) for i in range(8)]
    assert tail1 == tail2
    assert c2.policy.t == c1.policy.t


# ------------------------------------------------- incentive mechanisms

def test_periodic_auction_budget_ledger_accounting():
    """Per-round re-auction accounting: each re-auction spends from the
    REMAINING budget, the ledger is monotone, total spend never exceeds
    the budget (gmmfair pays bids within budget), and recruitment is
    cumulative (paid winners never evicted)."""
    spec = AuctionSpec(mechanism="gmmfair", budget=6.0, bid_model="exp4",
                       bid_seed=0, incentive="periodic_auction",
                       incentive_options={"every": 2})
    inc = incentive_from_spec(spec, n_clients=20, n_tasks=2)
    upd0 = inc.recruit(RoundContext(round=0, task_names=["a", "b"]))
    assert upd0 is not None and inc.auctions == 1
    spent0 = inc.spent
    assert 0 < spent0 <= 6.0
    assert inc.recruit(RoundContext(round=1, task_names=["a", "b"])) is None
    elig0 = np.asarray(upd0.eligibility, bool)
    upd2 = inc.recruit(RoundContext(round=2, task_names=["a", "b"]))
    if upd2 is not None:                         # budget may already be dry
        assert upd2.spent <= 6.0 - spent0 + 1e-9
        # cumulative recruitment: nobody is evicted
        assert (np.asarray(upd2.eligibility, bool) | elig0).sum() \
            == np.asarray(upd2.eligibility, bool).sum()
    assert inc.spent <= 6.0 + 1e-9
    # ledger state round-trips through JSON
    state = json.loads(json.dumps(inc.state_dict()))
    clone = incentive_from_spec(spec, n_clients=20, n_tasks=2)
    clone.load_state(state)
    assert clone.spent == inc.spent and clone.auctions == inc.auctions
    np.testing.assert_array_equal(np.asarray(clone.eligibility),
                                  np.asarray(inc.eligibility))


def test_periodic_auction_recruits_more_clients_over_time():
    s = two_task_spec(rounds=7, tau=2)
    s.auction = AuctionSpec(mechanism="greedy_within_budget", budget=3.0,
                            bid_seed=1, incentive="periodic_auction",
                            incentive_options={"every": 3})
    r = run_scenario(s)
    assert r.auction["auctions_run"] >= 2
    assert r.auction["total_spent"] <= 3.0 + 1e-9
    one = ScenarioSpec.from_json(s.to_json())
    one.auction.incentive = "one_shot"
    one.auction.incentive_options = {}
    r1 = run_scenario(one)
    # re-auctioning the leftover budget can only add eligibility
    assert r.auction["total_spent"] >= r1.auction["total_spent"] - 1e-9


def test_deferred_custom_incentive_and_round0_idempotence():
    """Contract fixes: a custom mechanism may return None from its FIRST
    recruit (everyone stays eligible until it auctions), and a mechanism
    keyed on ctx.round cannot double-auction round 0 even though
    run_scenario primes it before the engine's own round-0 call."""
    from repro.api import IncentiveMechanism, register_incentive

    rounds_seen = []

    @register_incentive("deferred_every2")
    class DeferredEvery2(IncentiveMechanism):
        def _recruit(self, ctx):
            rounds_seen.append(ctx.round)
            if ctx.round % 2 != 0:
                return None
            from repro.api import EligibilityUpdate

            self.auctions += 1
            elig = np.ones((self.n_clients, self.n_tasks), bool)
            return EligibilityUpdate(elig, None, 0.0, ctx.round)

    s = two_task_spec(rounds=4, tau=1)
    s.auction = AuctionSpec(mechanism="gmmfair", budget=5.0,
                            incentive="deferred_every2")
    r = run_scenario(s)
    # each round reaches _recruit exactly once (round 0 primed + engine
    # round-0 call deduplicated by the idempotence guard)
    assert rounds_seen == [0, 1, 2, 3]
    assert r.auction["auctions_run"] == 2
    # a mechanism that defers its first auction leaves everyone eligible
    rounds_seen.clear()

    @register_incentive("defer_first")
    class DeferFirst(IncentiveMechanism):
        def _recruit(self, ctx):
            return None                     # never auctions at all

    s2 = two_task_spec(rounds=2, tau=1)
    s2.auction = AuctionSpec(mechanism="gmmfair", budget=5.0,
                             incentive="defer_first")
    r2 = run_scenario(s2)                   # must not crash
    assert "take_up" not in r2.auction      # nothing auctioned
    assert r2.alloc_counts.sum() > 0        # everyone stayed eligible


def test_trainer_repeated_run_is_reproducible_with_stateful_policy():
    """MMFLTrainer.run() twice must be identical (the pre-policy
    contract): policy/incentive/eligibility state resets to the
    construction-time snapshot at the start of every run."""
    from repro.fed import MMFLTrainer, TrainConfig, standard_tasks

    tasks = standard_tasks(["synth-mnist", "synth-fmnist"], n_clients=8,
                           seed=0, n_range=(40, 60))
    cfg = TrainConfig(rounds=3, participation=0.5, tau=2, seed=0,
                      policy=UCBBanditPolicy(epsilon=0.3))
    tr = MMFLTrainer(tasks, cfg)
    h1 = tr.run()
    h2 = tr.run()
    np.testing.assert_array_equal(h1.acc, h2.acc)
    np.testing.assert_array_equal(h1.alloc, h2.alloc)


def test_run_scenario_rejects_non_positive_budget():
    for bad in (0.0, -3.0):
        s = two_task_spec(rounds=1, tau=1)
        s.auction = AuctionSpec(mechanism="maxmin_fair", budget=bad)
        with pytest.raises(ValueError, match="budget must be positive"):
            run_scenario(s)


# -------------------------------------------------- spec / registries

def test_policy_spec_json_roundtrip_and_legacy_load():
    s = two_task_spec(rounds=2, tau=1)
    s.policy = PolicySpec("ucb_bandit", {"epsilon": 0.2})
    s.auction = AuctionSpec(incentive="periodic_auction",
                            incentive_options={"every": 4})
    back = ScenarioSpec.from_json(s.to_json())
    assert back == s
    assert back.policy.options == {"epsilon": 0.2}
    # a legacy spec (no policy, no incentive fields) loads unchanged
    legacy = dict(tasks=[{"name": "synth-mnist"}],
                  auction={"mechanism": "gmmfair", "budget": 5.0})
    spec = ScenarioSpec.from_dict(legacy)
    assert spec.policy is None
    assert spec.auction.incentive == "one_shot"


def test_registry_error_paths():
    with pytest.raises(KeyError, match="ucb_bandit"):
        POLICIES.get("psychic")
    with pytest.raises(KeyError, match="one_shot"):
        INCENTIVES.get("bribe")
    s = two_task_spec(rounds=1, tau=1)
    s.policy = PolicySpec("psychic")
    with pytest.raises(KeyError, match="policy"):
        run_scenario(s)
    s2 = two_task_spec(rounds=1, tau=1)
    s2.auction = AuctionSpec(incentive="bribe")
    with pytest.raises(KeyError, match="incentive"):
        run_scenario(s2)


def test_policy_option_validation():
    with pytest.raises(ValueError, match="epsilon"):
        UCBBanditPolicy(epsilon=1.5)
    with pytest.raises(ValueError, match="gamma"):
        GradNormPolicy(gamma=0.0)
    with pytest.raises(ValueError, match="every"):
        INCENTIVES.get("periodic_auction")(every=0)


def test_legacy_wrapper_accepts_key_enum_and_callable():
    from repro.core.allocation import AllocationStrategy

    losses = np.array([0.2, 0.8])
    ctx = RoundContext(round=0, task_names=["a", "b"], losses=losses,
                       alpha=3.0)
    by_key = LegacyStrategyPolicy("fedfair").allocate(ctx)
    by_enum = LegacyStrategyPolicy(
        AllocationStrategy.FEDFAIR).allocate(ctx)
    np.testing.assert_array_equal(by_key, by_enum)
    custom = LegacyStrategyPolicy(
        lambda losses, alpha: np.array([0.0, 1.0])).allocate(ctx)
    np.testing.assert_allclose(custom, [0.0, 1.0])
    assert LegacyStrategyPolicy("round_robin").allocate(ctx) is None


# ------------------------------------- satellite: buffer sizing & sweeps

def test_backend_aware_default_buffer_size():
    import jax

    from repro.fed import resolve_buffer_size

    assert resolve_buffer_size(7, "vmap") == 7          # explicit wins
    assert resolve_buffer_size(None, "serial") == 4     # FedAST default
    expect = max(4, jax.device_count())
    assert resolve_buffer_size(None, "vmap") == expect
    assert resolve_buffer_size(None, "sharded") == expect
    # threads through the engine construction
    from repro.api import TASK_FAMILIES

    spec = two_task_spec(mode="async", total_arrivals=8, tau=1)
    assert spec.runtime.buffer_size is None
    spec.runtime.backend = "vmap"
    runner = TASK_FAMILIES.get("synthetic")().async_engine(spec)
    assert runner.engine.buffer_size == expect


def test_parallel_sweep_matches_sequential_and_keeps_order():
    """Satellite: --jobs N sweeps run grid points in worker processes and
    return the SAME payload (same run order, same curves) as the
    sequential driver."""
    from repro.api import sweep_scenarios

    base = two_task_spec(rounds=2, tau=1)
    grid = {"allocation.strategy": ["fedfair", "random"]}
    seq = sweep_scenarios(base, grid)
    par = sweep_scenarios(base, grid, max_workers=2)
    assert [r["name"] for r in seq["runs"]] == \
        [r["name"] for r in par["runs"]]
    for a, b in zip(seq["runs"], par["runs"]):
        assert a["overrides"] == b["overrides"]
        np.testing.assert_array_equal(np.asarray(a["result"]["loss"]),
                                      np.asarray(b["result"]["loss"]))


@pytest.mark.parametrize("platforms", [None, "tpu", "cpu,tpu"])
def test_parallel_sweep_refuses_unless_cpu(monkeypatch, platforms):
    """Worker processes cannot share an accelerator: --jobs N needs
    JAX_PLATFORMS=cpu, read from the environment before any worker
    starts."""
    from repro.api import sweep_scenarios

    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    grid = {"allocation.strategy": ["fedfair", "random"]}
    with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
        sweep_scenarios(two_task_spec(rounds=1, tau=1), grid, max_workers=2)
