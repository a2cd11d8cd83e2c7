"""DeepSeek-V2 in the system against the benchmark's plain reference
(``benchmarks/chip/families/deepseek_v2.py``, loaded by path: the one
reference of the architecture), on the CPU at the family's ``TINY`` size:
MLA with yarn rope, the dense first layer, the drop-free expert layer over
the chip's share of the routed experts beside the shared experts, and the
per-sequence balance loss.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import gmm
from repro.models import get_api
from repro.models import layers, moe
from repro.models.attention import mla_softmax_scale

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
SEED = 4_294_967_311
# the published values: yarn's ramp over rope dims 10..23 of 64, and the
# softmax scale 192**-0.5 * (0.1 * 0.707 * ln 40 + 1)**2
YARN_BOUNDS, SOFTMAX_SCALE = (10, 23), 0.114722


@pytest.fixture(scope="module")
def ref():
    if str(CHIP) not in sys.path:
        sys.path.insert(0, str(CHIP))
    import reference

    return reference


@pytest.fixture(scope="module")
def fam(ref):
    return ref.family({"family": "deepseek_v2"})


@pytest.fixture(scope="module")
def published():
    return json.loads((CHIP / "configs" / "deepseek-v2-lite.json").read_text())


@pytest.fixture(scope="module")
def tiny(fam, published):
    return dict(published, **fam.TINY)


@pytest.fixture(scope="module")
def weights(ref, tiny):
    return ref.make_weights_fn(tiny)(ref.seed_key(SEED))


def _tokens(cfg, rows=2, seq=32):
    return np.random.default_rng(SEED).integers(0, cfg["vocab_size"], (rows, seq)).astype(np.int32)


def test_system_loss_and_grads_match_the_reference(fam, tiny, weights):
    mc = fam.model_config(tiny, "deepseek-v2-tiny")
    api = get_api(mc)
    init = jax.eval_shape(lambda k: api.init_params(k, mc), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: tuple(a.shape), init) == fam.weight_shapes(tiny)
    toks = jnp.asarray(_tokens(tiny))
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(api.loss_fn, has_aux=True)(
            weights, mc, {"tokens": toks, "labels": toks})
        want, want_g = jax.value_and_grad(lambda p: fam.loss_fn(p, tiny, toks))(weights)
    assert float(aux["aux"]) > 0
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    for (path, g), w in zip(jax.tree.flatten_with_path(grads)[0], jax.tree.leaves(want_g)):
        gn, wn = float(jnp.linalg.norm(g.ravel())), float(jnp.linalg.norm(w.ravel()))
        assert abs(gn - wn) <= 1e-4 * wn, (jax.tree_util.keystr(path), gn, wn)


def _sparse_input(tiny, weights):
    f = jax.tree.map(lambda a: a[0], weights["moe_layers"]["ffn"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 32, tiny["hidden_size"]))
    return f, h


def _all_experts(f, n):
    """The uncut layer's routed experts: the held ones, then fresh ones."""
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    grow = {k: jnp.concatenate([f[k], 0.1 * jax.random.normal(
        kk, (n - f[k].shape[0],) + f[k].shape[1:])]) for k, kk in zip(("gate", "up", "down"), keys)}
    return dict(f, **grow)


@pytest.mark.parametrize("side", ["system", "reference"])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(fam, tiny, weights, side):
    """Shares 0-3 of 16 routed experts, each held 4 at a time: their routed
    parts, with the shared experts (which every share computes alike)
    counted once, give the layer with all 16 held."""
    f, h = _sparse_input(tiny, weights)
    E, G = fam.router_experts(tiny), tiny["n_routed_experts"]
    full = _all_experts(f, E)

    def share(s):
        return dict(full, **{k: full[k][s * G:(s + 1) * G] for k in ("gate", "up", "down")})

    shared = moe._shared(f, h)
    if side == "system":
        mc = fam.model_config(tiny, "deepseek-v2-tiny")
        whole, _ = moe.moe_ffn(full, mc.replace(experts_held=()), h)
        parts = [moe.moe_ffn(share(s), mc.replace(experts_held=(s * G, (s + 1) * G)), h)[0] - shared
                 for s in range(E // G)]
    else:
        whole = fam.routed(tiny, h, full)[0] + shared
        parts = [fam.routed(tiny, h, share(s), first=s * G)[0] for s in range(E // G)]
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-5, atol=1e-5)


def test_every_token_on_the_same_experts_loses_nothing(fam, tiny, weights):
    """Every token chooses held experts 0-2: the drop-free layer computes
    all of them (the reference's dense sum), where a capacity of 1.25
    times the even share drops most."""
    f, h = _sparse_input(tiny, weights)
    h = jnp.abs(h) + 0.1
    k, E = tiny["num_experts_per_tok"], fam.router_experts(tiny)
    router = -jnp.ones((tiny["hidden_size"], E)) / tiny["hidden_size"]
    f = dict(f, router=router.at[:, :k].set(jnp.arange(1, k + 1) / tiny["hidden_size"]))
    mc = fam.model_config(tiny, "deepseek-v2-tiny")
    got, _ = moe.moe_ffn(f, mc, h)
    want = fam.routed(tiny, h, f)[0] + moe._shared(f, h)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    full = _all_experts(f, E)
    dropping, _ = moe.moe_ffn(full, mc.replace(experts_held=(), capacity_factor=1.25), h)
    assert not np.allclose(dropping, fam.routed(tiny, h, full)[0] + moe._shared(f, h), atol=1e-3)


@pytest.mark.parametrize("side", ["system", "reference"])
def test_yarn_at_the_published_values(fam, published, side):
    if side == "system":
        cfg = get_config("deepseek-v2-lite-16b")
        bounds = layers.yarn_bounds(cfg.qk_rope_head_dim, cfg.rope_theta, cfg)
        scale = mla_softmax_scale(cfg)
        inv = layers.rope_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, layers.yarn_of(cfg))
    else:
        bounds, scale = fam.yarn_bounds(published), fam.softmax_scale(published)
        inv = None
    assert bounds == YARN_BOUNDS
    assert abs(scale - SOFTMAX_SCALE) < 1e-6      # the value to its six places
    if inv is not None:
        f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
        r = np.clip((np.arange(32) - 10) / 13, 0, 1)
        np.testing.assert_allclose(inv, f / 40 * r + f * (1 - r), rtol=1e-6)


def _rope_before(x, positions, theta):
    """apply_rope as it was before yarn, for the dense models."""
    hd = x.shape[-1]
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


@pytest.mark.parametrize("shape,theta", [((2, 16, 3, 64), 10_000.0), ((2, 16, 128), 1e6),
                                         ((1, 8, 2, 16), 10_000.0)])
def test_dense_rope_is_bit_identical(shape, theta):
    x = jax.random.normal(jax.random.PRNGKey(3), shape)
    pos = jnp.broadcast_to(jnp.arange(shape[1]), shape[:2])
    assert np.array_equal(np.asarray(layers.apply_rope(x, pos, theta)),
                          np.asarray(_rope_before(x, pos, theta)))


def _every_expert_on_every_token(x, gid, w, gate, up, down):
    """The layer without a sort or a grouped matmul: every held expert on
    every token, weighted by the choices that picked it (a choice of G, an
    expert not held here, picks none)."""
    pick = jnp.sum(jax.nn.one_hot(gid, gate.shape[0]) * w[..., None], axis=-2)
    h = jax.nn.silu(jnp.einsum("td,gdf->tgf", x, gate)) * jnp.einsum("td,gdf->tgf", x, up)
    return jnp.einsum("tgf,gfd,tg->td", h, down, pick)


@pytest.mark.parametrize("weights_batched", [True, False])
def test_ragged_experts_match_the_jnp_path_under_vmap_and_grad(weights_batched):
    """The layer (``jax.lax.ragged_dot`` with the cohort folded into one
    problem) against every expert on every token in plain jnp, per cohort
    row and in the gradient, with the experts batched or shared across
    rows."""
    B, T, d, f, G, k = 3, 16, 8, 12, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (B, T, d))
    gid = jax.random.randint(ks[1], (B, T, k), 0, G + 1)
    w = jax.random.uniform(ks[2], (B, T, k))
    lead = (B,) if weights_batched else ()
    gate, up = (jax.random.normal(kk, lead + (G, d, f)) for kk in ks[3:5])
    down = jax.random.normal(ks[5], lead + (G, f, d))
    ax = 0 if weights_batched else None

    def run(fn):
        def loss(x, w, gate, up, down, gid):
            return jnp.sum(jnp.sin(fn(x, gid, w, gate, up, down)))

        return jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                        in_axes=(0, 0, ax, ax, ax, 0))(x, w, gate, up, down, gid)

    with jax.default_matmul_precision("highest"):
        got, want = run(gmm.routed_experts), run(_every_expert_on_every_token)
    for g, wv in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, wv, rtol=1e-5, atol=1e-4 * float(jnp.abs(wv).max()))


def test_trained_floats_of_the_cell_by_hand(fam, ref, published):
    d, ff, f, V = 2048, 10944, 1408, 12800
    attn = d * 16 * 192 + d * 576 + 512 + 512 * 16 * 256 + 16 * 128 * d
    assert attn == 13_763_072
    dense = attn + 2 * d + 3 * d * ff
    sparse = attn + 2 * d + d * 64 + 8 * 3 * d * f + 3 * d * 2 * f
    assert (dense, sparse) == (81_007_104, 100_405_760)
    n = fam.trained_params(published, ref.padded_vocab(published["vocab_size"]))
    assert n == 2 * V * d + d + dense + 4 * sparse == 535_060_992
    # the routed experts count at their expectation: 6 choices, 8 of 64 held
    per_token = fam.matmul_params(published)
    assert per_token == 5 * (attn - 512) + 3 * d * ff + 4 * (
        d * 64 + 3 * d * f * (2 + 6 * 8 / 64)) + d * V


_RAGGED_DOT = jax.lax.ragged_dot


@jax.custom_vjp
def _gmm_unwritten(x, w, sizes):
    """``ragged_dot`` as the TPU's grouped kernel gives it: rows past the
    groups, in the result and in the rows' gradient, hold whatever the
    memory held (NaN here)."""
    return _past_groups_nan(_RAGGED_DOT(x, w, sizes), sizes)


def _past_groups_nan(y, sizes):
    return jnp.where((jnp.arange(y.shape[0]) < jnp.sum(sizes))[:, None], y, jnp.nan)


def _gmm_unwritten_fwd(x, w, sizes):
    return _gmm_unwritten(x, w, sizes), (x, w, sizes)


def _gmm_unwritten_bwd(res, g):
    x, w, sizes = res
    dx, dw = jax.vjp(lambda x, w: _RAGGED_DOT(x, w, sizes), x, w)[1](g)
    return _past_groups_nan(dx, sizes), dw, None


_gmm_unwritten.defvjp(_gmm_unwritten_fwd, _gmm_unwritten_bwd)


def test_rows_past_the_groups_reach_neither_tokens_nor_gradients(monkeypatch):
    """Some choices go to experts not held here: their rows sort past the
    groups, where the TPU's kernel leaves its result and its transpose
    unwritten. With ``ragged_dot`` made to do the same, the layer and its
    gradient are still every expert on every token's."""
    T, d, f, G, k = 16, 8, 12, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (T, d))
    gid = jax.random.randint(ks[1], (T, k), 0, G + 1)
    assert int(jnp.sum(gid == G)) > 0
    w = jax.random.uniform(ks[2], (T, k))
    gate, up = (jax.random.normal(kk, (G, d, f)) for kk in ks[3:5])
    down = jax.random.normal(ks[5], (G, f, d))

    def run(fn):
        loss = lambda x, w, gate, up, down: jnp.sum(jnp.sin(fn(  # noqa: E731
            x, gid, w, gate, up, down)))
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(x, w, gate, up, down)

    with jax.default_matmul_precision("highest"):
        want = run(_every_expert_on_every_token)
        monkeypatch.setattr(jax.lax, "ragged_dot", _gmm_unwritten)
        got = run(gmm.routed_experts)
    for g, wv in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, wv, rtol=1e-5, atol=1e-4 * float(jnp.abs(wv).max()))
