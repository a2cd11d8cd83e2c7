"""MMFL training launcher: concurrent fair training of multiple
architectures with FedFairMMFL client-task allocation.

A thin CLI over the scenario API: flags (or a ``--spec scenario.json``
file) build a ``ScenarioSpec``, and ``repro.api.run_scenario`` drives the
sync round loop or the async FedAST-style engine behind the shared Engine
protocol. On the CPU container it runs reduced ("tiny") configs
end-to-end; on a real cluster the same code path jits against
make_production_mesh() with the partition specs from repro.sharding (see
dryrun.py, which proves every arch x shape lowers).

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.train \\
      --archs smollm-135m,qwen3-0.6b,qwen2-moe-a2.7b \\
      --preset tiny --rounds 20 --clients 16 --alpha 3
  PYTHONPATH=src python -m repro.launch.train \\
      --spec examples/specs/tiny_two_task.json
"""
from __future__ import annotations

import argparse
import functools
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.api import (AllocationSpec, ClientPopulationSpec, PolicySpec,
                       RuntimeSpec, ScenarioSpec, TaskSpec, run_scenario)
from repro.configs import get_config, smoke_config
from repro.core.allocation import AllocationStrategy
from repro.fed.trainer import task_round_key
from repro.models import get_api
from repro.optim import adamw


def make_dataset(key, cfg, n_clients, shards_per_client, seq, seed=0):
    """Synthetic per-client token shards with client-specific structure, so
    losses are heterogeneous across clients (non-iid)."""
    rng = np.random.default_rng(seed)
    vocab = cfg.vocab_size
    data = []
    for k in range(n_clients):
        # each client k prefers a vocabulary band (non-iid)
        lo = rng.integers(0, max(1, vocab // 2))
        hi = min(vocab, lo + vocab // 2)
        toks = rng.integers(lo, hi, size=(shards_per_client, seq))
        data.append(toks.astype(np.int32))
    return np.stack(data)           # (K, shards, seq)


def arch_features(cfg, toks):
    """Model-input dict from token rows, handling the vlm/audio extras.
    Works on any leading batch shape (the arch adapters vmap it per
    cohort row)."""
    batch = {"tokens": toks, "labels": toks}
    if cfg.arch_type == "vlm":
        seq = toks.shape[-1]
        batch["img_embeds"] = jnp.zeros(
            toks.shape[:-1] + (cfg.n_img_tokens, cfg.d_model))
        batch["tokens"] = toks[..., : seq - cfg.n_img_tokens]
        batch["labels"] = toks[..., : seq - cfg.n_img_tokens]
    if cfg.arch_type == "audio":
        batch["frames"] = jnp.zeros(
            toks.shape[:-1] + (cfg.enc_frames, cfg.d_model))
    return batch


@functools.lru_cache(maxsize=None)
def arch_local_fn(api, cfg, tau: int, local_lr: float):
    """ONE cohort row's local FedAvg work for an arch task: tau SGD steps
    on the row's batch from the global params — the ``local_fn`` the
    ExecutionBackend API executes serially, vmapped, or sharded. Returns
    (updated_params, mean local loss); deterministic given the batch (the
    PRNG key slot is unused).

    lru_cached on the (hashable, frozen) api/cfg pair so every engine
    built for the same architecture shares ONE function object — the
    backends key their process-wide jit caches on it, so repeated engine
    construction (sweeps, benchmarks) reuses compilations instead of
    leaking a fresh jitted copy per engine."""

    def local_fn(params, key, client_batch):
        del key

        def step(p, _):
            (l, _), g = jax.value_and_grad(
                api.loss_fn, has_aux=True)(p, cfg, client_batch)
            p = jax.tree.map(
                lambda pp, gg: (pp - local_lr * gg).astype(pp.dtype),
                p, g)
            return p, l

        p, ls = jax.lax.scan(step, params, None, length=tau)
        return p, ls.mean()

    return local_fn


_ARCH_EVAL_CACHE: dict = {}


def make_arch_eval(task, data):
    """Jitted eval pair for an arch task on a held-out shard: (loss,
    next-token top-1 accuracy). Accuracy gives ArchFamily tasks a real
    accuracy curve, so ``fairness_report`` unifies across the synthetic
    and LM families instead of falling back to loss-only.

    Cached on (cfg, eval data) — data arrays are unhashable, so the key
    carries the bytes of the small held-out slice — for the same reason
    the local_fns are lru_cached: repeated engine construction must reuse
    jits, not leak fresh compiled copies."""
    cfg, api = task["cfg"], task["api"]
    slice_ = data[: min(8, data.shape[0]), 0]
    key = (cfg, slice_.shape, slice_.tobytes())
    hit = _ARCH_EVAL_CACHE.get(key)
    if hit is not None:
        return hit
    n_eval = min(8, data.shape[0])
    eval_toks = jnp.asarray(data[:n_eval, 0] % cfg.vocab_size)
    feats = arch_features(cfg, eval_toks)
    # next-token probe: prefill on all-but-last tokens, predict the last
    probe = dict(feats)
    probe["tokens"] = feats["tokens"][:, :-1]
    probe["labels"] = feats["labels"][:, :-1]
    target = feats["tokens"][:, -1]

    @jax.jit
    def eval_loss(params):
        return api.loss_fn(params, cfg, feats)[0]

    @jax.jit
    def eval_acc(params):
        logits, _ = api.prefill_fn(params, cfg, probe)
        pred = jnp.argmax(logits[:, -1, :], axis=-1)
        return jnp.mean((pred == target).astype(jnp.float32))

    _ARCH_EVAL_CACHE[key] = (eval_loss, eval_acc)
    return eval_loss, eval_acc


@functools.lru_cache(maxsize=None)
def arch_shard_local_fn(api, cfg, tau: int, local_lr: float):
    """``arch_local_fn`` over a client's raw token shards (the async
    adapters' unit of work): features are built inside, so the stacked
    cohort input is just the (n, shards, seq) token array. Cached for the
    same reason as ``arch_local_fn``."""
    row_fn = arch_local_fn(api, cfg, tau, local_lr)

    def local_fn(params, key, toks):
        return row_fn(params, key, arch_features(cfg, toks))

    return local_fn


def server_opt():
    """The arch tasks' server optimizer — ONE definition, consumed by both
    ``build_task`` (opt_state init) and ``arch_fused_step`` (the update
    rule), so the hyper-parameters cannot silently drift apart."""
    return adamw(lr=3e-3, max_grad_norm=1.0)


@functools.lru_cache(maxsize=None)
def arch_fused_step(api, cfg):
    """tau=1 local steps == weighted gradient aggregation (core/mmfl):
    ONE fused adamw server step on the mixed p_k-weighted batch. Returns
    (train_step, opt_local_fn) — the latter wraps the step as a
    single-unit "cohort" (state = the (params, opt) pair) so the engine
    dispatches it through the same ExecutionBackend seam. The serial
    backend's row program puts the cohort axis on the new state inside
    the step's own program, and the engine's ``take_row`` donates that row
    back as the state; the step itself donates nothing, since the old
    state is still read after it (update norms). lru_cached like
    ``arch_local_fn`` so engines for the same config share one jit."""
    opt = server_opt()

    @jax.jit
    def train_step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(
            api.loss_fn, has_aux=True)(params, cfg, batch)
        new_p, new_o = opt.update(params, grads, opt_state)
        return loss, new_p, new_o

    def opt_local_fn(state, key, batch):
        del key
        params_, opt_ = state
        loss, new_p, new_o = train_step(params_, opt_, batch)
        return (new_p, new_o), loss

    return train_step, opt_local_fn


def build_task(arch: str, preset: str, seq: int, batch: int, tau: int = 1,
               local_lr: float = 5e-3):
    cfg = smoke_config(arch) if preset == "tiny" else get_config(arch)
    cfg = cfg.replace(ssm_chunk=min(cfg.ssm_chunk, max(8, seq // 4)))
    api = get_api(cfg)
    # crc32 (not hash()) keying: PYTHONHASHSEED-independent, so model init
    # is reproducible across processes
    params = api.init_params(
        jax.random.PRNGKey(zlib.crc32(arch.encode()) % 2**31), cfg)
    if tau <= 1:
        opt_state = server_opt().init(params)
        train_step, opt_local_fn = arch_fused_step(api, cfg)
    else:
        # TRUE FedAvg: each cohort row runs tau local SGD steps from the
        # global params; execution AND Pallas-kernel aggregation dispatch
        # through the ExecutionBackend API (the engine calls run_cohort
        # on "local_fn" below, then backend.aggregate). No server
        # optimizer, so no moments held on the device.
        opt_state, train_step, opt_local_fn = None, None, None

    return {"cfg": cfg, "api": api, "params": params, "opt": opt_state,
            "step": train_step, "tau": tau,
            "local_fn": arch_local_fn(api, cfg, max(tau, 1), local_lr),
            "opt_local_fn": opt_local_fn,
            "batch": batch, "seq": seq}


def assemble_batch(task, data, client_ids, weights, rng):
    cfg = task["cfg"]
    B, seq = task["batch"], task["seq"]
    reps = int(np.ceil(B / max(len(client_ids), 1)))
    rows = np.tile(client_ids, reps)[:B]
    shard_ix = rng.integers(0, data.shape[1], size=B)
    toks = data[rows, shard_ix][:, :seq] % cfg.vocab_size
    w = np.asarray(weights)
    w_rows = np.tile(w, reps)[:B]
    w_rows = w_rows / max(w_rows.sum(), 1e-9)
    batch = {"tokens": jnp.asarray(toks),
             "labels": jnp.asarray(toks),
             "client_weights": jnp.asarray(w_rows, jnp.float32)}
    if cfg.arch_type == "vlm":
        batch["img_embeds"] = jnp.zeros((B, cfg.n_img_tokens, cfg.d_model))
        batch["tokens"] = batch["tokens"][:, :seq - cfg.n_img_tokens]
        batch["labels"] = batch["labels"][:, :seq - cfg.n_img_tokens]
    if cfg.arch_type == "audio":
        batch["frames"] = 0.02 * jnp.asarray(
            rng.standard_normal((B, cfg.enc_frames, cfg.d_model)),
            jnp.float32)
    return batch


class ArchAsyncTask:
    """AsyncTask adapter for one architecture: tau local SGD steps on the
    completing client's token shards. The one-client rule is exposed as
    ``local_fn`` + ``client_batch``, so the AsyncMMFLEngine's flush groups
    dispatch through the pluggable ExecutionBackend (serial / vmap /
    sharded) exactly like the synthetic tasks — same event queue, buffers,
    and staleness machinery."""

    def __init__(self, name, task_idx, task, data, tau=2, local_lr=5e-3):
        self.name = name
        self.task_idx = task_idx
        self.task = task
        self.data = data                      # (K, shards, seq)
        self.n_clients = data.shape[0]
        self.p_k = np.ones(self.n_clients) / self.n_clients
        self.work = 1.0
        cfg, api = task["cfg"], task["api"]
        self._cfg = cfg
        # a client's "batch" is its full shard stack (shards, seq)
        self.local_fn = arch_shard_local_fn(api, cfg, tau, local_lr)
        self._eval, self._eval_acc = make_arch_eval(task, data)

    def init(self, seed):
        del seed
        return self.task["params"]

    def client_batch(self, seed, version, client_ids):
        from repro.api.backend import ClientBatch

        key = task_round_key(seed, self.task_idx, version)
        ids = np.asarray(client_ids)
        keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(
            jnp.asarray(ids))
        toks = jnp.asarray(self.data[ids] % self._cfg.vocab_size)
        return ClientBatch(ids, keys, (toks,))

    def update(self, params, seed, version, client_ids):
        from repro.api.backend import CohortTask, get_backend

        return get_backend("vmap").run_cohort(
            CohortTask(self.name, params, self.local_fn),
            self.client_batch(seed, version, client_ids)).updates

    def evaluate(self, params) -> float:
        return spans.fetch(self._eval(params))

    def accuracy(self, params) -> float:
        """Next-token top-1 accuracy on the held-out shard (the arch
        family's analogue of the synthetic tasks' test accuracy)."""
        return spans.fetch(self._eval_acc(params))


def build_scenario(args) -> ScenarioSpec:
    """Map the CLI flags onto a ScenarioSpec (the args are the legacy
    interface; the spec is the canonical one)."""
    archs = args.archs.split(",")
    task_opts = {"preset": args.preset, "seq": args.seq,
                 "batch": args.batch, "tau": args.tau}
    return ScenarioSpec(
        name="launch-train",
        seed=args.seed,
        data_seed=args.seed,
        tasks=[TaskSpec(name=a, family="arch", options=dict(task_opts))
               for a in archs],
        clients=ClientPopulationSpec(
            n_clients=args.clients,
            participation=args.participation,
            speed_profile=args.speed_profile,
            speed_spread=args.speed_spread,
            arrival_process=args.arrival_process,
            population=args.population,
            population_options=json.loads(args.population_options)
            if args.population_options else {}),
        allocation=AllocationSpec(strategy=args.strategy, alpha=args.alpha),
        policy=PolicySpec(name=args.policy) if args.policy else None,
        runtime=RuntimeSpec(
            mode="async" if args.async_mode else "sync",
            backend=args.backend,
            rounds=args.rounds,
            tau=args.tau,
            total_arrivals=args.arrivals,
            buffer_size=args.buffer,
            beta=args.beta,
            buffer_controller=args.buffer_controller,
            aggregator=args.aggregator,
            aggregator_options=json.loads(args.aggregator_options)
            if args.aggregator_options else {},
            cost_model=args.cost_model,
            cost_model_options=json.loads(args.cost_model_options)
            if args.cost_model_options else {},
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            checkpoint_keep=args.checkpoint_keep,
            resume=args.resume))


def main():
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", default=None,
                    help="ScenarioSpec JSON file; overrides all other "
                         "flags (the declarative interface)")
    ap.add_argument("--archs", default="smollm-135m,qwen3-0.6b")
    ap.add_argument("--preset", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--alpha", type=float, default=3.0)
    ap.add_argument("--strategy", default="fedfair",
                    choices=[s.value for s in AllocationStrategy])
    ap.add_argument("--policy", default=None,
                    help="stateful allocation policy (POLICIES key, e.g. "
                         "ucb_bandit | grad_norm); default: the bit-exact "
                         "legacy wrapper for --strategy")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--participation", type=float, default=0.5)
    ap.add_argument("--tau", type=int, default=1,
                    help=">1: true FedAvg with tau local steps per client")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="serial",
                    help="cohort execution backend (serial | vmap | "
                         "sharded | registered BACKENDS key)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="full-state checkpoints for BOTH engines: every "
                         "N rounds (sync) or N flushes (async)")
    ap.add_argument("--checkpoint-every", "--ckpt-every", type=int,
                    default=10, dest="checkpoint_every",
                    help="rounds (sync) / flushes (async) between "
                         "checkpoints")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    dest="checkpoint_keep",
                    help="checkpoint retention: keep the newest N complete "
                         "steps in --checkpoint-dir, GC older ones")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in "
                         "--checkpoint-dir (async resume is "
                         "event-for-event identical to an uninterrupted "
                         "run)")
    ap.add_argument("--async", action="store_true", dest="async_mode",
                    help="event-driven async engine (FedAST-style buffered "
                         "staleness-aware aggregation) instead of "
                         "lockstep rounds")
    ap.add_argument("--arrivals", type=int, default=64,
                    help="async: client completions to process")
    ap.add_argument("--buffer", type=int, default=None,
                    help="async: aggregate every B arrivals per task "
                         "(default: backend-aware — 4 on serial, "
                         "device count on vmap/sharded)")
    ap.add_argument("--beta", type=float, default=0.5,
                    help="async: staleness discount exponent")
    ap.add_argument("--aggregator", default=None,
                    help="server aggregation rule (fedavg | fedavgm | "
                         "fedadam | fedyogi | fedmedian | trimmed_mean | "
                         "registered AGGREGATORS key); default: the "
                         "bit-exact legacy weighted mean")
    ap.add_argument("--aggregator-options", default=None,
                    help="JSON dict of aggregator constructor options, "
                         "e.g. '{\"lr\": 0.1}' for --aggregator fedadam")
    ap.add_argument("--cost-model", default=None, dest="cost_model",
                    help="client cost model (constant | device_tiers | "
                         "lognormal_straggler | trace_replay | registered "
                         "COST_MODELS key): simulated compute+comm "
                         "latency per job — async completion times, sync "
                         "per-round clock; default: the bit-exact legacy "
                         "timing (constant)")
    ap.add_argument("--cost-model-options", default=None,
                    dest="cost_model_options",
                    help="JSON dict of cost-model constructor options, "
                         "e.g. '{\"sigma\": 0.8, \"dropout_prob\": 0.05}' "
                         "for --cost-model lognormal_straggler")
    ap.add_argument("--buffer-controller", default=None,
                    help="async: adaptive per-task buffer sizing "
                         "(static | staleness_target | arrival_rate | "
                         "registered BUFFER_CONTROLLERS key); default: "
                         "static (the legacy fixed knob)")
    ap.add_argument("--speed-profile", default="bimodal",
                    choices=["uniform", "bimodal", "lognormal"])
    ap.add_argument("--speed-spread", type=float, default=4.0)
    ap.add_argument("--arrival-process", default="always_on",
                    help="async availability plugin "
                         "(always_on | bursty | poisson | registered)")
    ap.add_argument("--population", default=None,
                    help="client population plugin (vectorized | "
                         "registered POPULATIONS key): struct-of-arrays "
                         "per-client state, bit-exact with the legacy "
                         "dict path and required for very large N")
    ap.add_argument("--population-options", default=None,
                    dest="population_options",
                    help="JSON dict of population constructor options, "
                         "e.g. '{\"lazy_data\": true}' to materialize "
                         "synthetic client shards on first dispatch")
    args = ap.parse_args()

    spec = (ScenarioSpec.load(args.spec) if args.spec
            else build_scenario(args))
    names = [t.name for t in spec.tasks]
    if spec.runtime.mode == "async":
        from repro.fed.async_engine import resolve_buffer_size

        buf = resolve_buffer_size(spec.runtime.buffer_size,
                                  spec.runtime.backend)
        print(f"ASYNC MMFL: {names} buffer={buf} "
              f"controller={spec.runtime.buffer_controller or 'static'} "
              f"aggregator={spec.runtime.aggregator or 'fedavg'} "
              f"cost_model={spec.runtime.cost_model or 'constant'} "
              f"beta={spec.runtime.beta} "
              f"profile={spec.clients.speed_profile} "
              f"arrival={spec.clients.arrival_process} "
              f"on {jax.device_count()} device(s)")
    else:
        print(f"MMFL concurrent training: {names} "
              f"[backend={spec.runtime.backend} "
              f"aggregator={spec.runtime.aggregator or 'fedavg'}] on "
              f"{jax.device_count()} device(s)")

    result = run_scenario(spec, verbose=True)

    if result.mode == "async":
        print(f"processed {int(result.arrivals.sum())} arrivals "
              f"({len(result.time)} aggregations) in "
              f"{result.wall_time:.1f}s wall, "
              f"{result.virtual_time:.1f} virtual")
    print("final losses:", {n: round(v, 3)
                            for n, v in result.final_loss.items()})


if __name__ == "__main__":
    main()
