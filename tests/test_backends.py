"""ExecutionBackend API: serial/vmap/sharded parity through run_scenario,
registry error paths, fedavg kernel validation, sweep driver, arch
accuracy curves."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    BACKENDS,
    ClientPopulationSpec,
    CohortTask,
    RuntimeSpec,
    ScenarioSpec,
    SerialBackend,
    TaskSpec,
    get_backend,
    register_backend,
    run_scenario,
    sweep_scenarios,
)

ALL_BACKENDS = ("serial", "vmap", "sharded")


def two_task_spec(backend="serial", mode="sync", **runtime_kw):
    return ScenarioSpec(
        name="bk",
        seed=0,
        tasks=[TaskSpec("synth-mnist", options={"n_range": [40, 60]}),
               TaskSpec("synth-fmnist", options={"n_range": [40, 60]})],
        clients=ClientPopulationSpec(n_clients=10, participation=1.0),
        runtime=RuntimeSpec(mode=mode, backend=backend, **runtime_kw))


def _assert_tree_close(a, b, atol=1e-6):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


# ----------------------------------------------------------------- registry

def test_backend_registry_contents_and_unknown_key():
    assert set(ALL_BACKENDS) <= set(BACKENDS.names())
    with pytest.raises(KeyError, match="serial"):
        BACKENDS.get("turbo")
    with pytest.raises(KeyError, match="backend"):
        get_backend("turbo")


def test_unknown_backend_fails_fast_in_run_scenario():
    spec = two_task_spec(rounds=1)
    spec.runtime.backend = "turbo"
    with pytest.raises(KeyError, match="backend"):
        run_scenario(spec)


def test_spec_backend_field_roundtrip_and_legacy_load():
    spec = two_task_spec(backend="vmap", rounds=2)
    back = ScenarioSpec.from_json(spec.to_json())
    assert back == spec and back.runtime.backend == "vmap"
    # pre-backend specs (no field) load unchanged and default to serial
    legacy = {"tasks": [{"name": "synth-mnist"}],
              "runtime": {"mode": "sync", "rounds": 1}}
    assert ScenarioSpec.from_dict(legacy).runtime.backend == "serial"


def test_custom_backend_registration_dispatches():
    calls = []

    @register_backend("counting")
    class CountingBackend(SerialBackend):
        def run_cohort(self, task_state, client_batch, rng=None):
            calls.append(len(client_batch))
            return super().run_cohort(task_state, client_batch, rng)

    r = run_scenario(two_task_spec(backend="counting", rounds=2, tau=2))
    assert calls and sum(calls) == int(r.arrivals.sum())


# ------------------------------------------------------------------- parity

@pytest.mark.parametrize("backend", ["vmap", "sharded"])
def test_sync_backend_parity_vs_serial(backend):
    """Acceptance: every backend reproduces the serial reference ≤1e-6
    (loss curves AND final params) through run_scenario."""
    base = run_scenario(two_task_spec("serial", rounds=3, tau=2))
    got = run_scenario(two_task_spec(backend, rounds=3, tau=2))
    np.testing.assert_allclose(got.loss, base.loss, atol=1e-6)
    np.testing.assert_allclose(got.acc, base.acc, atol=1e-6)
    np.testing.assert_array_equal(got.alloc, base.alloc)
    for p, q in zip(base.params, got.params):
        _assert_tree_close(p, q)


@pytest.mark.parametrize("backend", ["vmap", "sharded"])
def test_async_backend_parity_vs_serial(backend):
    kw = dict(mode="async", total_arrivals=20, buffer_size=4, tau=2)
    base = run_scenario(two_task_spec("serial", **kw))
    got = run_scenario(two_task_spec(backend, **kw))
    np.testing.assert_allclose(got.loss, base.loss, atol=1e-6)
    for p, q in zip(base.params, got.params):
        _assert_tree_close(p, q)


def test_serial_backend_matches_reference_cohort_bitexact():
    """The serial backend's per-client loop is bit-exact with the library
    cohort entry point (fold_in keying makes per-client results
    independent of cohort batching)."""
    from repro.fed import standard_tasks
    from repro.fed.trainer import (cohort_update, fed_client_batch,
                                   fed_local_fn, init_task_models,
                                   task_round_key)

    tasks = standard_tasks(["synth-mnist"], n_clients=6, seed=0,
                           n_range=(40, 60))
    p0 = init_task_models(tasks, jax.random.PRNGKey(0), 64, 2)[0]
    key = task_round_key(0, 0, 0)
    ids = np.arange(6)
    ref = cohort_update(p0, key, tasks[0], ids, 3, 0.1, 32)
    got = SerialBackend().run_cohort(
        CohortTask("t", p0, fed_local_fn(3, 0.1, 32)),
        fed_client_batch(tasks[0], key, ids)).updates
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _state_fn(params, key, x):
    """A one-client step over a (params, opt)-like state, keyed noise in."""
    w = params["w"] * x.sum() + jax.random.normal(key, params["w"].shape)
    return {"w": w, "opt": (params["opt"][0] - x[:2], params["opt"][1] + 1)}, jnp.square(x).mean()


@pytest.mark.parametrize("n", [1, 3])
def test_serial_backend_rows_are_the_local_fn_outputs(n, monkeypatch):
    """The serial backend's cohort is bit for bit each client's jitted
    ``local_fn`` output, stacked. A one-row cohort is the row program's
    own output: no eager stack or concatenate runs on the state."""
    from repro.api import ClientBatch, backend

    params = {"w": jnp.arange(12.0).reshape(3, 4), "opt": (jnp.ones(2), jnp.int32(3))}
    keys = jax.random.split(jax.random.PRNGKey(5), n)
    x = jnp.asarray(np.random.default_rng(n).normal(size=(n, 4)), jnp.float32)
    batch = ClientBatch(np.arange(n), keys, (x,))
    task = CohortTask("t", params, _state_fn)
    SerialBackend().run_cohort(task, batch)          # trace outside the count

    calls, rows = [], []
    for fname in ("stack", "concatenate"):
        real = getattr(jnp, fname)
        monkeypatch.setattr(jnp, fname, lambda *a, _r=real, _f=fname, **k: (
            calls.append(_f), _r(*a, **k))[1])
    real_row = backend._jit_row
    monkeypatch.setattr(backend, "_jit_row", lambda fn: lambda *a: (
        rows.append(real_row(fn)(*a)), rows[-1])[1])
    got = SerialBackend().run_cohort(task, batch)

    one = jax.jit(_state_fn)
    want = [one(params, keys[i], x[i]) for i in range(n)]
    want_upd = jax.tree.map(lambda *ls: np.stack(ls), *[u for u, _ in want])
    assert jax.tree.structure(got.updates) == jax.tree.structure(want_upd)
    for g, w in zip(jax.tree.leaves(got.updates), jax.tree.leaves(want_upd)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), w)
    np.testing.assert_array_equal(np.asarray(got.losses), np.stack([l for _, l in want]))
    assert len(rows) == n
    if n == 1:
        assert calls == []
        for g, r in zip(jax.tree.leaves((got.updates, got.losses)), jax.tree.leaves(rows[0])):
            assert g is r
    else:
        # one concatenate a leaf of the rows (3 state leaves and the loss)
        assert calls == ["concatenate"] * 4


def _fused_engine(rounds=3):
    """A tiny two-task tau = 1 arch engine (the fused AdamW step as a
    one-row cohort) whose backend records each cohort it runs."""
    from repro.api.engine import ArchFamily

    opts = {"preset": "tiny", "seq": 16, "batch": 2, "tau": 1}
    spec = ScenarioSpec(
        name="fused", seed=0,
        tasks=[TaskSpec("smollm-135m", family="arch", options=opts),
               TaskSpec("qwen3-0.6b", family="arch", options=opts)],
        clients=ClientPopulationSpec(n_clients=4, participation=1.0),
        runtime=RuntimeSpec(mode="sync", rounds=rounds))
    engine = ArchFamily().sync_engine(spec)
    seen = []
    run_cohort = engine.backend.run_cohort

    def recorded(task_state, client_batch, rng=None):
        res = run_cohort(task_state, client_batch, rng)
        seen.append((task_state.name, client_batch.data[0], res))
        return res

    engine.backend.run_cohort = recorded
    return engine, seen


def test_fused_fold_state_matches_train_step_in_order():
    """Three tau = 1 rounds leave params and Adam state bit for bit as the
    plain ``train_step`` leaves them on the same batches, in order."""
    engine, seen = _fused_engine()
    state = {a: (t["params"], t["opt"]) for a, t in engine.tasks.items()}
    engine.run()
    assert {name for name, _, _ in seen} == set(engine.tasks)
    for name, data, res in seen:
        t = engine.tasks[name]
        loss, p, o = t["step"](*state[name], jax.tree.map(lambda v: v[0], data))
        state[name] = (p, o)
        np.testing.assert_array_equal(np.asarray(res.losses), np.asarray(loss)[None])
    for name, t in engine.tasks.items():
        want, got = jax.tree.leaves(state[name]), jax.tree.leaves((t["params"], t["opt"]))
        assert len(want) == len(got)
        for w, g in zip(want, got):
            assert w.shape == g.shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fused_fold_norm_is_the_steps_displacement():
    """``want_norm`` is read before the row is donated: the l2 norm of the
    new params less the old, as ``stacked_delta_norms`` gives it."""
    from repro.api.policy import stacked_delta_norms

    engine, _ = _fused_engine()
    t = engine.tasks["qwen3-0.6b"]
    before = t["params"]
    _, norm = engine._run_task_round("qwen3-0.6b", np.arange(2), np.random.default_rng(0),
                                     want_norm=True)
    want = stacked_delta_norms(jax.tree.map(lambda x: x[None], t["params"]), before)[0]
    assert norm > 0 and norm == want


def test_fused_fold_counts_one_fold_program_per_fold(tmp_path):
    """Under a capture, each tau = 1 fold is one ``fold_programs``."""
    from repro import spans

    engine, seen = _fused_engine(rounds=2)
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        engine.run()
    assert len(seen) >= 2 and spans.counters()["fold_programs"] == len(seen)
    spans.reset()


def test_fused_fold_donates_the_row():
    """The fold donates the one-row result: its state leaves are deleted,
    its losses (read by callers after the fold) are not."""
    probe = jnp.ones(4)
    jax.jit(lambda x: x + 1, donate_argnums=0)(probe)
    if not probe.is_deleted():
        pytest.skip(f"{jax.default_backend()} does not honour buffer donation")
    engine, seen = _fused_engine(rounds=1)
    engine.run()
    for _, _, res in seen:
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(res.updates))
        assert not res.losses.is_deleted()
    for t in engine.tasks.values():
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves((t["params"], t["opt"])))


def test_backend_aggregate_matches_server_aggregate():
    from repro.fed.server import aggregate

    cohort = {"w": jnp.arange(24.0).reshape(4, 3, 2)}
    weights = jnp.asarray(np.array([0.1, 0.4, 0.2, 0.3], np.float32))
    ref = aggregate(cohort, weights)
    for backend in ALL_BACKENDS:
        got = get_backend(backend).aggregate(cohort, weights)
        _assert_tree_close(ref, got)


def test_backend_aggregate_custom_normalizer():
    """The async engine normalises staleness-discounted weights by the
    UNDISCOUNTED sum — the normalizer hook must honour that."""
    cohort = jnp.ones((3, 4))
    out = get_backend("serial").aggregate(
        cohort, jnp.asarray([1.0, 1.0, 1.0]), normalizer=6.0)
    np.testing.assert_allclose(np.asarray(out), 0.5, rtol=1e-6)


def test_async_engine_refuses_an_adapter_without_local_fn():
    """The async engine has one task protocol (local_fn + client_batch):
    an adapter without local_fn is refused at construction, by name,
    instead of failing later inside backend dispatch."""
    from repro.fed import AsyncConfig, AsyncMMFLEngine, standard_tasks
    from repro.fed.async_engine import AsyncTask, FedAsyncTask

    tasks = standard_tasks(["synth-mnist"], n_clients=6, seed=0,
                           n_range=(40, 60))
    cfg = AsyncConfig(total_arrivals=6, buffer_size=3, tau=2, seed=0)

    class NoLocalFn(AsyncTask):
        def __init__(self):
            self.name, self.n_clients = "bare", 6
            self.p_k, self.work = tasks[0].p_k, 1.0

    with pytest.raises(ValueError, match=r"'bare' \(NoLocalFn\).*local_fn"):
        AsyncMMFLEngine([FedAsyncTask(tasks[0], 0, cfg), NoLocalFn()], cfg)
    # the protocol's own adapter is accepted and runs
    assert len(AsyncMMFLEngine([FedAsyncTask(tasks[0], 0, cfg)],
                               cfg).run().time) > 0


# ------------------------------------------------------------ fedavg kernel

def test_fedavg_pallas_interpret_auto_selects_platform():
    from repro.kernels.fedavg import fedavg_pallas

    st = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                     jnp.float32)
    w = jnp.asarray(np.full(4, 0.25, np.float32))
    auto = fedavg_pallas(st, w)                # interpret resolved inside
    ref = fedavg_pallas(st, w, interpret=True)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                               atol=1e-6)


def _mixed_cohort(k):
    """A cohort tree with leaves of ranks 1-3 in f32 and bf16, one of them
    ravelled with its last two axes swapped (256 lanes last, not 5): 3,827
    floats a row, a multiple of neither 128 nor 2048."""
    rng = np.random.default_rng(k)
    leaf = lambda shape, dt: jnp.asarray(rng.normal(size=(k,) + shape), dt)  # noqa: E731
    return {"w": leaf((50, 45), jnp.float32), "b": leaf((37,), jnp.bfloat16),
            "blocks": [leaf((3, 4, 10), jnp.float32), leaf((2, 70), jnp.bfloat16),
                       leaf((256, 5), jnp.float32)]}


@pytest.mark.parametrize("k", [1, 3, 4])
def test_compiled_fold_matches_eager_fold_and_jnp(k):
    """The one-program fold is bit for bit the eager composition it
    replaces (ravel, the kernel at a 2,048-lane tile, unravel) and within
    f32 rounding of the jnp weighted sum, in each leaf's own dtype."""
    from jax.flatten_util import ravel_pytree

    from repro.api.backend import _pallas_aggregate, _weighted_sum_jnp
    from repro.kernels.fedavg import fedavg_pallas

    cohort = _mixed_cohort(k)
    w = jnp.asarray(np.random.default_rng(10 + k).random(k), jnp.float32)
    w = w / w.sum()
    got = _pallas_aggregate(cohort, w)
    flat = jax.vmap(lambda p: ravel_pytree(p)[0])(cohort)
    _, unravel = ravel_pytree(jax.tree.map(lambda leaf: leaf[0], cohort))
    eager = unravel(fedavg_pallas(flat, w, blk=2048))
    want = _weighted_sum_jnp(cohort, w)
    assert jax.tree.structure(got) == jax.tree.structure(cohort)
    for g, e, j in zip(jax.tree.leaves(got), jax.tree.leaves(eager), jax.tree.leaves(want)):
        assert g.dtype == j.dtype and g.shape == j.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
        if g.dtype == jnp.float32:
            np.testing.assert_allclose(np.asarray(g), np.asarray(j), atol=1e-6)


def test_compiled_fold_compiles_once_per_tree():
    from repro.api.backend import _pallas_aggregate

    w = jnp.full(3, 1 / 3, jnp.float32)
    _pallas_aggregate(_mixed_cohort(3), w)
    size = _pallas_aggregate._cache_size()
    again = jax.tree.map(lambda leaf: leaf + 1, _mixed_cohort(3))
    jax.block_until_ready(_pallas_aggregate(again, w * 0.5))
    assert _pallas_aggregate._cache_size() == size


def test_fold_programs_counts_each_compiled_aggregate(tmp_path, monkeypatch):
    """``fold_programs`` counts each aggregate that takes the compiled fold
    (the path on TPU/GPU), once per call and only under a capture."""
    from repro import spans
    from repro.api import backend

    monkeypatch.setattr(backend, "_cohort_sum", lambda: backend._pallas_aggregate)
    cohort, w = _mixed_cohort(4), np.ones(4, np.float32)
    spans.reset()
    backend.VmapBackend().aggregate(cohort, w)
    assert "fold_programs" not in spans.counters()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            backend.VmapBackend().aggregate(cohort, w)
    assert spans.counters()["fold_programs"] == 3
    spans.reset()


def test_fedavg_pallas_validates_shapes():
    from repro.kernels.fedavg import fedavg_pallas

    with pytest.raises(ValueError, match="stacked"):
        fedavg_pallas(jnp.zeros((2, 3, 4)), jnp.zeros(2))
    with pytest.raises(ValueError, match="weights"):
        fedavg_pallas(jnp.zeros((2, 8)), jnp.zeros(3))
    with pytest.raises(ValueError, match="weights"):
        fedavg_pallas(jnp.zeros((2, 8)), jnp.zeros((2, 2)))


# ------------------------------------------------------------- sweep driver

def test_sweep_scenarios_backend_x_allocation_grid():
    merged = sweep_scenarios(
        two_task_spec(rounds=2, tau=2),
        {"runtime.backend": ["serial", "vmap"],
         "allocation.strategy": ["fedfair", "random"]})
    assert len(merged["runs"]) == 4
    json.dumps(merged)                          # JSON-native
    combos = {(r["overrides"]["runtime.backend"],
               r["overrides"]["allocation.strategy"])
              for r in merged["runs"]}
    assert combos == {("serial", "fedfair"), ("serial", "random"),
                      ("vmap", "fedfair"), ("vmap", "random")}
    # same-(seed, strategy) points differ only in backend => same curves
    by = {(r["overrides"]["runtime.backend"],
           r["overrides"]["allocation.strategy"]):
          np.asarray(r["result"]["loss"]) for r in merged["runs"]}
    np.testing.assert_allclose(by[("vmap", "fedfair")],
                               by[("serial", "fedfair")], atol=1e-6)


def test_sweep_unknown_override_path_fails_fast():
    with pytest.raises(AttributeError, match="no field"):
        sweep_scenarios(two_task_spec(rounds=1),
                        {"runtime.warp_speed": [1]})
    with pytest.raises(TypeError, match="list"):
        sweep_scenarios(two_task_spec(rounds=1),
                        {"runtime.backend": "serial"})


# ------------------------------------------------------- arch accuracy curve

@pytest.mark.parametrize("mode,kw", [
    ("sync", dict(rounds=2)),
    ("async", dict(total_arrivals=4, buffer_size=2)),
])
def test_arch_family_reports_accuracy_curve(mode, kw):
    """Satellite: ArchFamily tasks carry an eval-accuracy curve, so
    fairness_report unifies across synthetic and LM families."""
    spec = ScenarioSpec(
        name="arch-acc",
        tasks=[TaskSpec("smollm-135m", family="arch",
                        options={"preset": "tiny", "seq": 16, "batch": 2,
                                 "tau": 1})],
        clients=ClientPopulationSpec(n_clients=4, participation=1.0),
        runtime=RuntimeSpec(mode=mode, **kw))
    r = run_scenario(spec)
    assert r.acc is not None and len(r.acc)
    assert np.all((r.acc >= 0.0) & (r.acc <= 1.0))
    for k in ("min_acc", "var_acc", "cosine_uniformity", "worst_task"):
        assert k in r.fairness
