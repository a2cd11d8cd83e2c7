import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers, compiles, and fits — without allocating a single model byte.

For each combination we build ShapeDtypeStruct stand-ins (weak-type-correct,
sharding-annotated) for params, optimizer state, batches and KV caches, then
    lowered  = jax.jit(step, out_shardings=..., donate...).lower(*sds)
    compiled = lowered.compile()
and record memory_analysis(), cost_analysis() and the collective schedule
parsed from the post-SPMD HLO (launch/hlo_analysis.py) into a JSON record
(``--out``).

NOTE: the XLA_FLAGS line above MUST precede any jax import — jax locks the
host device count at first init. Smoke tests / benches import repro.* and
see 1 device; only this entry point sees 512.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_production_mesh
from repro.models import get_api
from repro.optim import adamw
from repro.sharding import partition as part


# Best-known settings from the EXPERIMENTS.md §Perf hillclimbs — MEASURED
# winners only. The hidden-dim activation resharding ('dmodel') wins for
# d_model >= ~4k and for SSD-bearing stacks but REGRESSES small models
# (smollm: 1.4s -> 17.2s memory term), so it is gated on width, not family.
# Baselines stay paper-faithful; pass --tuned to apply these.
TUNED_TRAIN = {
    "zamba2-7b": {"ssm_chunk": 128, "activation_shard": "dmodel",
                  "microbatches": 4},
    "xlstm-1.3b": {"ssm_chunk": 512, "activation_shard": "dmodel",
                   "microbatches": 4},
    "qwen1.5-110b": {"activation_shard": "dmodel", "microbatches": 4},
    "qwen3-0.6b": {"activation_shard": "dmodel"},   # coll 3.95 -> 3.56
    "qwen2-moe-a2.7b": {"pad_experts_to": 64, "microbatches": 2},
    # smollm/qwen1.5-0.5b/phi3/whisper/deepseek-train: baseline best
}
TUNED_DECODE_MLA = {"mla_absorb": True, "mla_cache_shard": "seq"}
# prefill: measured winners only — train knobs do NOT transfer blindly
# (xlstm c512 regresses 2.4x at prefill: no backward, so the decay-matrix
# traffic is not amortised by remat; see EXPERIMENTS.md)
TUNED_PREFILL = {
    "qwen2-moe-a2.7b": {"pad_experts_to": 64},    # 6.58 -> 4.24s
    "zamba2-7b": {"ssm_chunk": 128},
}


def tuned_overrides_for(arch: str, shape_name: str) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        return dict(TUNED_TRAIN.get(arch, {}))
    if shape.kind == "prefill":
        return dict(TUNED_PREFILL.get(arch, {}))
    if shape.kind == "decode" and cfg.use_mla:
        return dict(TUNED_DECODE_MLA)
    return {}


def tuned_config(arch: str, shape_name: str, overrides=None):
    """Dry-run configuration: bf16 params, remat for training, grouped MoE
    dispatch, sliding-window KV for the 500k decode shape."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    kw = dict(param_dtype="bfloat16")
    if shape.kind == "train":
        kw["remat"] = True
    if cfg.is_moe:
        # dispatch groups aligned with the data-parallel degree so each
        # group's top-C selection stays local to one mesh row
        dp = 16 if shape.global_batch % 16 == 0 and shape.global_batch > 1 \
            else 1
        kw["moe_groups"] = dp
    if shape_name == "long_500k" and cfg.arch_type != "ssm":
        kw["sliding_window"] = 4096
    if overrides:
        kw.update(overrides)
    return cfg.replace(**kw), shape


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def batch_specs(cfg, shape, mesh):
    """ShapeDtypeStructs for the model inputs of train/prefill."""
    B, S = shape.global_batch, shape.seq_len
    dp = part.dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    bs = dp if B % dp_size == 0 and B > 1 else None
    dt = jnp.dtype(cfg.param_dtype)
    S_text = S - cfg.n_img_tokens if cfg.arch_type == "vlm" else S
    batch = {
        "tokens": _sds((B, S_text), jnp.int32, mesh, P(bs, None)),
        "labels": _sds((B, S_text), jnp.int32, mesh, P(bs, None)),
    }
    if shape.kind == "train":
        batch["client_weights"] = _sds((B,), jnp.float32, mesh, P(bs))
    if cfg.arch_type == "vlm":
        batch["img_embeds"] = _sds((B, cfg.n_img_tokens, cfg.d_model), dt,
                                   mesh, P(bs, None, None))
    if cfg.arch_type == "audio":
        batch["frames"] = _sds((B, cfg.enc_frames, cfg.d_model), dt,
                               mesh, P(bs, None, None))
    return batch


def param_sds(api, cfg, mesh):
    shapes = jax.eval_shape(
        lambda k: api.init_params(k, cfg), jax.random.key(0))
    specs = part.tree_param_specs(shapes, cfg)
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp), shapes, specs), specs


def opt_sds(params_sds, param_specs, mesh):
    def mom(s):
        return jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                    sharding=s.sharding)
    return {
        "mu": jax.tree.map(mom, params_sds),
        "nu": jax.tree.map(mom, params_sds),
        "count": _sds((), jnp.int32, mesh, P()),
    }


def cache_sds(api, cfg, params_shapes, mesh, batch_size, length):
    dt = jnp.dtype(cfg.param_dtype)
    shapes = jax.eval_shape(
        lambda: api.init_cache_fn(params_shapes, cfg, batch_size, length,
                                  dt))
    return jax.tree_util.tree_map_with_path(
        lambda p, s: _sds(s.shape, s.dtype, mesh,
                          part.cache_spec(p, s, mesh, batch_size)), shapes)


def setup_ctx(cfg, mesh):
    part.clear_sharding_ctx()
    part.set_axis_sizes(mesh)
    dp = part.dp_axes(mesh)
    act = {"seq": P(dp, "model", None),
           "dmodel": P(dp, None, "model"),
           "none": None}[cfg.activation_shard]
    kw = {"logits": part.named(mesh, P(dp, None, "model")),
          "mla_cache_shard": cfg.mla_cache_shard}
    if act is not None:
        kw["activation"] = part.named(mesh, act)
    part.set_sharding_ctx(**kw)


def build_step(arch, shape_name, mesh, overrides=None):
    """Returns (fn, sds_args, donate, out_shardings_or_None, cfg)."""
    cfg, shape = tuned_config(arch, shape_name, overrides)
    api = get_api(cfg)
    setup_ctx(cfg, mesh)
    p_sds, p_specs = param_sds(api, cfg, mesh)

    if shape.kind == "train":
        opt = adamw(lr=1e-4)
        o_sds = opt_sds(p_sds, p_specs, mesh)
        b_sds = batch_specs(cfg, shape, mesh)

        def train_step(params, opt_state, batch):
            if cfg.microbatches > 1:
                n = cfg.microbatches

                def resh(t):
                    return t.reshape((n, t.shape[0] // n) + t.shape[1:])

                mb = jax.tree.map(resh, batch)

                def acc_step(acc, b):
                    (l, _), g = jax.value_and_grad(
                        api.loss_fn, has_aux=True)(params, cfg, b)
                    return jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), acc, g), l

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                gacc, ls = jax.lax.scan(acc_step, zeros, mb)
                grads = jax.tree.map(
                    lambda g, p: (g / n).astype(p.dtype), gacc, params)
                loss = ls.mean()
            else:
                (loss, _), grads = jax.value_and_grad(
                    api.loss_fn, has_aux=True)(params, cfg, batch)
            new_p, new_o = opt.update(params, grads, opt_state)
            return loss, new_p, new_o

        out_sh = (NamedSharding(mesh, P()),
                  jax.tree.map(lambda s: s.sharding, p_sds),
                  jax.tree.map(lambda s: s.sharding, o_sds))
        return train_step, (p_sds, o_sds, b_sds), (0, 1), out_sh, cfg

    if shape.kind == "prefill":
        b_sds = batch_specs(cfg, shape, mesh)

        def prefill_step(params, batch):
            return api.prefill_fn(params, cfg, batch)

        return prefill_step, (p_sds, b_sds), (), None, cfg

    # decode: one token against a seq_len cache
    B, S = shape.global_batch, shape.seq_len
    cache_len = min(S, cfg.sliding_window) if cfg.sliding_window else S
    c_sds = cache_sds(api, cfg, p_sds, mesh, B, cache_len)
    dp = part.dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    bs = dp if B % dp_size == 0 and B > 1 else None
    tok = _sds((B, 1), jnp.int32, mesh, P(bs, None))
    pos = _sds((), jnp.int32, mesh, P())

    def decode_step(params, caches, token, position):
        return api.decode_fn(params, cfg, token, position, caches)

    out_sh = (NamedSharding(mesh, P(bs, None, "model")),
              jax.tree.map(lambda s: s.sharding, c_sds))
    return decode_step, (p_sds, c_sds, tok, pos), (1,), out_sh, cfg


def run_dryrun(arch: str, shape_name: str, multi_pod: bool,
               overrides=None, keep_hlo=False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "n_devices": int(n_dev), "ok": False}
    if overrides:
        rec["overrides"] = {k: str(v) for k, v in overrides.items()}
    try:
        fn, sds, donate, out_sh, cfg = build_step(arch, shape_name, mesh,
                                                  overrides)
        jitted = jax.jit(fn, donate_argnums=donate, out_shardings=out_sh)
        t0 = time.time()
        with mesh:
            lowered = jitted.lower(*sds)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)
        rec["memory"] = ha.memory_summary(compiled)
        xla = ha.cost_summary(compiled)
        rec["xla_cost_analysis"] = {k: xla.get(k) for k in
                                    ("flops", "bytes", "transcendentals")}
        txt = compiled.as_text()
        walked = ha.analyze_hlo(txt)           # trip-count-aware
        coll = walked["collectives"]
        coll_tpu = walked["collectives_tpu"]
        rec["flops"] = walked["flops"]
        rec["bytes"] = walked["bytes"]
        rec["while_trips"] = walked["while_trips"]
        rec["collectives"] = {"bytes_by_op": coll.bytes_by_op,
                              "count_by_op": coll.count_by_op,
                              "total_bytes": coll.total_bytes,
                              "tpu_corrected_bytes": coll_tpu.total_bytes,
                              "tpu_bytes_by_op": coll_tpu.bytes_by_op}
        rec["roofline"] = ha.roofline_terms(rec["flops"], rec["bytes"],
                                            coll_tpu.total_bytes)
        # model-level useful FLOPs: 6 * N_active * tokens (per device)
        from repro.models.model import active_param_count
        p_shapes = jax.eval_shape(
            lambda k: get_api(cfg).init_params(k, cfg), jax.random.key(0))
        n_active = active_param_count(p_shapes, cfg)
        n_total = sum(x.size for x in jax.tree.leaves(p_shapes))
        shape = INPUT_SHAPES[shape_name]
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.kind != "decode" else 1)
        factor = 6 if shape.kind == "train" else 2
        rec["params_total"] = int(n_total)
        rec["params_active"] = int(n_active)
        rec["model_flops_per_device"] = factor * n_active * tokens / n_dev
        rec["useful_flop_ratio"] = (rec["model_flops_per_device"]
                                    / max(rec["flops"], 1.0))
        if keep_hlo:
            rec["hlo_len"] = len(txt)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001
        import traceback
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    finally:
        part.clear_sharding_ctx()
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (e.g. mla_absorb=True)")
    ap.add_argument("--tuned", action="store_true",
                    help="apply the §Perf best-known settings per family")
    args = ap.parse_args()
    overrides = {}
    if args.tuned:
        overrides.update(tuned_overrides_for(args.arch, args.shape))
    for ov in args.override:
        k, _, v = ov.partition("=")
        overrides[k] = json.loads(v) if v[:1] in "0123456789tf[{\"" else v
    rec = run_dryrun(args.arch, args.shape, args.multi_pod,
                     overrides or None)
    js = json.dumps(rec, indent=2, default=str)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)
    print(js if rec["ok"] else js)
    if rec["ok"]:
        mem = rec.get("memory", {})
        print(f"\nOK {args.arch} x {args.shape} mesh={rec['mesh']} "
              f"flops/dev={rec['flops']:.3e} "
              f"coll={rec['collectives']['total_bytes']:.3e}B "
              f"bottleneck={rec['roofline']['bottleneck']}")
    else:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
