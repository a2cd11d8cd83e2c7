"""MMFLCoordinator (scale-level orchestration) behaviour."""
import numpy as np
import pytest

from repro.core.allocation import AllocationStrategy
from repro.core.mmfl import MMFLCoordinator


def test_allocation_covers_active_fraction():
    c = MMFLCoordinator(["a", "b"], n_clients=20, participation=0.5, seed=0)
    c.report("a", 1.0)
    c.report("b", 1.0)
    alloc = c.next_round()
    total = sum(len(v) for v in alloc.values())
    assert total == 10


def test_worse_task_gets_more_clients_on_average():
    c = MMFLCoordinator(["easy", "hard"], n_clients=50, alpha=3.0, seed=1)
    c.report("easy", 0.2)
    c.report("hard", 0.8)
    counts = np.zeros(2)
    for _ in range(30):
        alloc = c.next_round()
        counts += [len(alloc["easy"]), len(alloc["hard"])]
    assert counts[1] > counts[0] * 2


def test_unreported_losses_fall_back_to_uniformish():
    c = MMFLCoordinator(["a", "b"], n_clients=10, seed=2)
    alloc = c.next_round()      # no losses yet
    assert sum(len(v) for v in alloc.values()) == 10


def test_eligibility_matrix_respected():
    elig = np.zeros((10, 2), bool)
    elig[:5, 0] = True
    elig[5:, 1] = True
    c = MMFLCoordinator(["a", "b"], n_clients=10, seed=3,
                        eligibility=elig)
    c.report("a", 0.5)
    c.report("b", 0.5)
    for _ in range(5):
        alloc = c.next_round()
        assert all(i < 5 for i in alloc["a"])
        assert all(i >= 5 for i in alloc["b"])


def test_client_weights_normalised():
    c = MMFLCoordinator(["a"], n_clients=10, seed=4)
    w = c.client_weights(np.array([1, 3, 5]))
    assert np.isclose(w.sum(), 1.0)
    assert len(w) == 3


def test_round_robin_strategy():
    c = MMFLCoordinator(["a", "b", "c"], n_clients=9, seed=5,
                        strategy=AllocationStrategy.ROUND_ROBIN)
    for t in ("a", "b", "c"):
        c.report(t, 1.0)
    alloc = c.next_round()
    counts = sorted(len(v) for v in alloc.values())
    assert sum(counts) == 9
    assert counts[-1] - counts[0] <= 1      # balanced


def test_assign_next_round_robin_total_with_restricted_eligibility():
    """Regression: the round-robin branch of assign_next must be total —
    it used to be able to fall through to the probabilistic path with
    probs=None (TypeError). With any eligibility pattern it must return
    an eligible task (or None), never raise."""
    elig = np.zeros((4, 3), bool)
    elig[0, 2] = True                      # only the last task
    elig[1, 0] = elig[1, 1] = True
    elig[2] = True
    # client 3 eligible for nothing
    c = MMFLCoordinator(["a", "b", "c"], n_clients=4, seed=0,
                        strategy=AllocationStrategy.ROUND_ROBIN,
                        eligibility=elig)
    for _ in range(50):
        for i in range(4):
            s = c.assign_next(i)
            if i == 3:
                assert s is None
            else:
                assert s is not None and elig[i, s]


def test_state_dict_roundtrip_reproduces_allocations():
    """Checkpoint satellite: round counter + RNG stream + per-task stats
    survive state_dict/load_state, so a restored coordinator produces the
    exact allocation sequence of an uninterrupted one."""
    import json

    def fresh():
        c = MMFLCoordinator(["a", "b"], n_clients=12, participation=0.5,
                            seed=3)
        c.report("a", 0.4)
        c.report("b", 0.8)
        return c

    c1 = fresh()
    for _ in range(3):
        c1.next_round()
    state = json.loads(json.dumps(c1.state_dict()))   # JSON-serializable
    tail1 = [c1.next_round() for _ in range(3)]

    c2 = fresh()
    c2.load_state(state)
    assert c2._round == 3
    tail2 = [c2.next_round() for _ in range(3)]
    for a1, a2 in zip(tail1, tail2):
        assert a1.keys() == a2.keys()
        for k in a1:
            np.testing.assert_array_equal(a1[k], a2[k])


def test_load_state_refuses_a_partial_payload():
    """load_state reads only the full state_dict layout: a payload that
    lacks any of its keys raises before the coordinator changes."""
    c = MMFLCoordinator(["a", "b"], n_clients=4, seed=0)
    c.report("a", 0.4)
    c.next_round()
    before = c.state_dict()
    for key in before:
        partial = {k: v for k, v in before.items() if k != key}
        with pytest.raises(ValueError, match=key):
            c.load_state(partial)
    with pytest.raises(ValueError, match="rng_state"):
        c.load_state({"losses": {"a": 0.7, "b": 0.2}})
    assert c.state_dict() == before
