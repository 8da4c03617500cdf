"""`models/afmoe.py` (one chip's share of a sparse model with window
and full attention, trained) against the plain reference
`benchmarks/reference/afmoe.py`, at a small size with seeded weights,
on the CPU: loss and WHOLE gradient, the shares of guide section 4
adding up to the uncut layer, no pair dropped under an adversarial
router, and the bias that a rule moves and no optimizer touches."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import weights_afmoe
from benchmarks.reference import afmoe as ref
from ray_tpu.models import afmoe
from ray_tpu.parallel import moe

MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "route_scale": 2.826,
    "sliding_window": 8, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "mup_enabled": True, "load_balance_coeff": 1e-3}
HELD, SLICE = (2, 4), (0, 512)


def _config(**kw):
    return afmoe.AfmoeConfig.tiny(held=HELD, vocab_slice=SLICE,
                                  dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def setup():
    params = weights_afmoe.params(MODEL, HELD[1], SLICE[1], 7, 0.02)
    # weights of a size at which routing and attention are not flat
    params = jax.tree.map(lambda x: x * 8 if x.ndim >= 2 else x, params)
    bias = weights_afmoe.check_bias(MODEL, 7, 0.2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0, 512)
    want = ref.loss_and_grad(params, tokens, MODEL, HELD, SLICE, bias)
    return params, bias, tokens, want


def _rel(got, want):
    sq = lambda t: sum(float(jnp.sum(jnp.square(x)))  # noqa: E731
                       for x in jax.tree.leaves(t))
    return (sq(jax.tree.map(lambda a, b: a - b, got, want)) / sq(want)) ** 0.5


@pytest.mark.parametrize("how", [
    {}, {"attention": "flash", "interpret": True},
    {"kernel": True, "interpret": True},
    {"attention": "flash", "kernel": True, "interpret": True}],
    ids=["dense", "flash", "grouped-kernels", "flash-and-grouped-kernels"])
def test_loss_and_whole_gradient_are_the_references(setup, how):
    params, bias, tokens, ((l_ref, c_ref), g_ref) = setup
    cfg = _config(**how)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: afmoe.loss_fn(cfg, p, tokens, bias), has_aux=True)(params)
    assert abs(float(loss) - float(l_ref)) < 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(g_ref)
    assert _rel(grads, g_ref) < 1e-5
    # every leaf has a gradient of its own that matches: held experts,
    # router, gates, head norms, the slice's embedding and head
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(g_ref)):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * max(
            1.0, float(jnp.max(jnp.abs(b)))), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)
    np.testing.assert_array_equal(aux["counts"], c_ref)
    assert int(aux["held_pairs"].sum()) == int(
        np.asarray(c_ref)[:, HELD[0]:HELD[0] + HELD[1]].sum())


def test_the_bias_moves_the_pick_and_not_the_weights(setup):
    """With the bias in the weights, or out of the pick, the loss is
    another: the seeded bias tells the two apart."""
    params, bias, tokens, ((l_ref, _), _) = setup
    cfg = _config()
    no_bias = afmoe.loss_fn(cfg, params, tokens, jnp.zeros_like(bias))[0]
    assert abs(float(no_bias) - float(l_ref)) > 1e-4
    grad_b = jax.grad(lambda b: afmoe.loss_fn(cfg, params, tokens, b)[0])(bias)
    assert not np.asarray(grad_b).any()


# ---------------------------------------------------------------- shares
def _uncut_layer(seed=11, N=48, D=32, I=16, E=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    layer = {"router": jax.random.normal(ks[0], (D, E)),
             "e_gate": jax.random.normal(ks[1], (E, D, I)) * 0.3,
             "e_up": jax.random.normal(ks[2], (E, D, I)) * 0.3,
             "e_down": jax.random.normal(ks[3], (E, I, D)) * 0.3}
    return layer, jax.random.normal(ks[4], (N, D)), \
        jax.random.normal(ks[5], (E,)) * 0.3


def _share(layer, lo, count):
    return {"router": layer["router"],
            **{k: layer[k][lo:lo + count] for k in ("e_gate", "e_up",
                                                    "e_down")}}


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged", "kernels"])
def test_the_shares_add_up_to_the_uncut_layer(kernel):
    """Guide section 4: the routed parts of ALL the shares (8 experts
    as 4 chips of 2), with nothing shared counted twice, are the uncut
    reference's routed sum: output AND input-gradient."""
    layer, h, bias = _uncut_layer()
    E, count, top_k = 8, 2, 3
    dy = jax.random.normal(jax.random.PRNGKey(1), h.shape)

    def reference(h):
        w, idx = ref.route(h, layer["router"], bias, top_k, 2.826)
        return ref.routed(h, w, idx, layer, (0, E), lambda x: x)

    def shares(h):
        total, held_pairs = 0.0, 0
        for lo in range(0, E, count):
            y, stats = moe.dropless_moe_train(
                h, _share(layer, lo, count), bias, top_k=top_k, scale=2.826,
                route_eps=1e-20, dtype=jnp.float32, held=(lo, count),
                kernel=kernel, interpret=kernel)
            total, held_pairs = total + y, held_pairs + stats["held_pairs"]
        return total, held_pairs

    got, held_pairs = shares(h)
    assert int(held_pairs) == h.shape[0] * top_k  # every pair on one chip
    np.testing.assert_allclose(got, reference(h), atol=2e-5)
    g_got = jax.grad(lambda h: jnp.sum(shares(h)[0] * dy))(h)
    g_want = jax.grad(lambda h: jnp.sum(reference(h) * dy))(h)
    np.testing.assert_allclose(g_got, g_want, atol=1e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged", "kernels"])
def test_no_pair_is_dropped_when_every_pick_is_held(kernel):
    """An adversarial router: every token's whole top-k lies inside the
    held experts, four times the mean load a slab is sized for.  All
    the slabs run, every pair is computed, and the gradient is the
    reference's."""
    layer, h, _ = _uncut_layer(N=96)
    E, held, top_k = 8, (4, 2), 2
    bias = jnp.where((jnp.arange(E) >= 4) & (jnp.arange(E) < 6), 50.0, 0.0)
    dy = jax.random.normal(jax.random.PRNGKey(2), h.shape)
    share = _share(layer, *held)
    tile = moe.TRAIN_ROW_TILE if kernel else moe.ROW_TILE
    rows = moe.train_slab_rows(h.shape[0] * top_k, held[1], E, tile)
    run = lambda h, mats: moe.dropless_moe_train(  # noqa: E731
        h, {**share, **mats}, bias, top_k=top_k, scale=2.826,
        route_eps=1e-20, dtype=jnp.float32, held=held, kernel=kernel,
        interpret=kernel)
    mats = {k: share[k] for k in ("e_gate", "e_up", "e_down")}
    y, stats = run(h, mats)
    assert int(stats["held_pairs"]) == h.shape[0] * top_k
    assert int(stats["slabs"]) == -(-h.shape[0] * top_k // rows)
    if not kernel:
        assert int(stats["slabs"]) > 1  # 192 pairs in slabs of 128 rows

    def reference(h, mats):
        w, idx = ref.route(h, layer["router"], bias, top_k, 2.826)
        return ref.routed(h, w, idx, {**layer, **{
            k: jnp.zeros_like(layer[k]).at[held[0]:held[0] + held[1]].set(v)
            for k, v in mats.items()}}, (0, E), lambda x: x)

    np.testing.assert_allclose(y, reference(h, mats), atol=2e-5)
    got = jax.grad(lambda h, m: jnp.sum(run(h, m)[0] * dy), (0, 1))(h, mats)
    want = jax.grad(lambda h, m: jnp.sum(reference(h, m) * dy), (0, 1))(
        h, mats)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_slabs_are_twice_the_mean_load_and_cover_every_pair():
    assert moe.train_slab_rows(8192 * 8, 16, 128, 256) == 16384
    assert moe.train_slab_rows(64, 4, 8, 128) == 128      # never over all
    assert moe.train_slab_rows(192, 2, 8, 128) == 128
    assert -(-8192 * 8 // moe.train_slab_rows(8192 * 8, 16, 128, 256)) == 4


# ------------------------------------------------------------ the bias rule
def test_the_bias_follows_its_rule_outside_the_optimizer(setup):
    """Three steps: after each the bias is the reference's rule applied
    to the reference's counts of that step's batch at the step's
    parameters; it is in neither the gradient nor the optimizer's
    state, and weight decay does not reach it."""
    params, _, _, _ = setup
    cfg = _config()
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adamw(3e-3, b1=0.9, b2=0.95, weight_decay=0.1))
    state, opt_state = afmoe.init_state(cfg, params), opt.init(params)
    assert sum(x.size for x in jax.tree.leaves(opt_state)
               if hasattr(x, "size")) <= 2 * afmoe.num_params(params) + 8
    step = jax.jit(afmoe.make_train_step(cfg, opt))
    want = np.asarray(state["router_bias"])
    for i in range(3):
        tokens = jax.random.randint(jax.random.PRNGKey(100 + i), (2, 33),
                                    0, 512)
        _, counts = ref.loss(state["params"], tokens, MODEL, HELD, SLICE,
                             jnp.asarray(want))
        want = ref.bias_rule(want, counts, MODEL["load_balance_coeff"])
        state, opt_state, met = step(state, opt_state, tokens)
        np.testing.assert_allclose(state["router_bias"], want, atol=1e-7)
        assert set(met) == {"loss", "grad_norm", "held_pairs",
                            "expert_load_max", "expert_load_mean",
                            "bias_abs_max"}
        assert float(met["expert_load_mean"]) == 2 * 32 * 2 / 8
        assert float(met["bias_abs_max"]) == pytest.approx(
            np.abs(want).max())
    assert np.abs(want).max() > 0 and abs(want.sum()) < 1e-6  # centred
    # three steps of the rule and nothing else: multiples of half a step
    np.testing.assert_allclose(
        np.asarray(state["router_bias"]) / 5e-4,
        np.round(np.asarray(state["router_bias"]) / 5e-4), atol=1e-3)


def test_a_mesh_of_several_chips_is_refused():
    class Mesh:
        size = 4

    with pytest.raises(NotImplementedError, match="one chip"):
        afmoe.make_train_step(_config(), optax.sgd(0.1), mesh=Mesh())


def test_the_tree_the_axes_and_the_counts_agree():
    cfg = dataclasses.replace(_config(), held=(0, 4))
    params = afmoe.init_params(cfg, jax.random.PRNGKey(0))
    axes = afmoe.logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for p, a in zip(jax.tree.leaves(params), jax.tree.leaves(
            axes, is_leaf=lambda x: isinstance(x, tuple))):
        assert p.ndim == len(a)
    # the published widths: 27.26M of attention a layer, 705.5M held
    full = afmoe.AfmoeConfig(
        layer_types=(afmoe.SLIDING,) * 4 + (afmoe.FULL,), num_dense_layers=1,
        held=(0, 16), vocab_slice=(0, 25024))
    shapes = jax.eval_shape(lambda: afmoe.init_params(
        full, jax.random.PRNGKey(0)))
    assert round(afmoe.num_params(shapes) / 1e6, 1) == 705.5
    assert round(afmoe.active_params_per_token(full) / 1e6, 1) == 276.7


def test_a_token_outside_the_slice_embeds_to_zeros_and_has_no_target(setup):
    params, bias, tokens, _ = setup
    cfg = dataclasses.replace(_config(), vocab_slice=(100, 512))
    inside = jnp.clip(tokens, 100, 611)
    shifted = dataclasses.replace(_config(), vocab_slice=(0, 512))
    a = afmoe.loss_fn(cfg, params, inside, bias)[0]
    b = afmoe.loss_fn(shifted, params, inside - 100, bias)[0]
    assert abs(float(a) - float(b)) < 1e-6
    outside = afmoe.loss_fn(cfg, params, jnp.zeros_like(tokens), bias)[0]
    assert np.isfinite(float(outside))
