"""GPT-2 model tests (CPU, tiny config)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.parallel import MeshSpec, data_sharding, tree_shardings


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_param_count_formula(tiny):
    cfg, params = tiny
    n = gpt2.num_params(params)
    E, L, V, Ppos = cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_positions
    expected = (
        V * E
        + Ppos * E
        + L * (4 * E + 3 * E * E + 3 * E + E * E + E + 8 * E * E + 4 * E + E)
        + 2 * E
    )
    assert n == expected


def test_logical_tree_matches_params(tiny):
    cfg, params = tiny
    logical = gpt2.logical_axes(cfg)
    flat_p = jax.tree.structure(params)
    flat_l = jax.tree.structure(logical, is_leaf=lambda x: isinstance(x, tuple))
    assert flat_p == flat_l
    # every logical tuple rank matches the param rank
    def check(p, l):
        assert len(l) == p.ndim, f"{l} vs {p.shape}"
    jax.tree.map(check, params, logical, is_leaf=lambda x: isinstance(x, tuple))


def test_forward_shapes(tiny):
    cfg, params = tiny
    toks = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = gpt2.forward(cfg, params, toks)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_initial_loss_near_uniform(tiny):
    cfg, params = tiny
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)
    loss = float(gpt2.loss_fn(cfg, params, toks))
    assert abs(loss - np.log(cfg.vocab_size)) < 0.5


def test_training_reduces_loss(tiny):
    cfg, params = tiny
    opt = gpt2.default_optimizer(lr=1e-2, warmup_steps=1, total_steps=60)
    opt_state = opt.init(params)
    step = jax.jit(gpt2.make_train_step(cfg, opt))
    # overfit one small batch
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, cfg.vocab_size)
    first = None
    for i in range(40):
        params, opt_state, m = step(params, opt_state, toks)
        if first is None:
            first = float(m["loss"])
    last = float(m["loss"])
    assert last < first - 1.0, f"{first} -> {last}"


def test_sharded_train_step_matches_single(tiny):
    cfg, params = tiny
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build()
    opt = gpt2.default_optimizer(lr=1e-3, warmup_steps=1, total_steps=10)
    toks = jax.random.randint(jax.random.PRNGKey(3), (8, 33), 0, cfg.vocab_size)

    # single-device
    o1 = opt.init(params)
    s_single = jax.jit(gpt2.make_train_step(cfg, opt))
    p1, o1, m1 = s_single(params, o1, toks)

    # sharded
    shardings = tree_shardings(mesh, gpt2.logical_axes(cfg))
    ps = jax.tree.map(jax.device_put, params, shardings)
    os_ = opt.init(ps)
    ts = jax.device_put(toks, data_sharding(mesh))
    with mesh:
        s_shard = jax.jit(gpt2.make_train_step(cfg, opt, mesh))
        p2, o2, m2 = s_shard(ps, os_, ts)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(p1["wte"]), np.asarray(p2["wte"]), rtol=2e-2, atol=2e-4
    )


def test_ring_attention_model_variant(tiny):
    cfg, params = tiny
    mesh = MeshSpec(sp=4, dp=2).build()
    cfg_ring = dataclasses.replace(cfg, attention="ring")
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0, cfg.vocab_size)
    dense = gpt2.loss_fn(cfg, params, toks)
    with mesh:
        ringy = gpt2.loss_fn(cfg_ring, params, jax.device_put(toks, data_sharding(mesh)), mesh)
    np.testing.assert_allclose(float(dense), float(ringy), rtol=2e-2)


# ----------------------------------------------------------------------
# Mixtral (sparse MoE; SURVEY §2.5 expert parallelism first-class)
# ----------------------------------------------------------------------
def test_mixtral_forward_and_loss():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mixtral

    cfg = mixtral.MixtralConfig.tiny()
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                cfg.vocab_size)
    logits, aux = mixtral.forward(cfg, params, tokens[:, :-1])
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    assert float(aux["load_balance_loss"]) > 0.0

    loss, metrics = mixtral.loss_fn(cfg, params, tokens)
    assert jnp.isfinite(loss)
    # a fresh router routes near-uniformly: aux ~= 1.0 for top-1 frac
    assert 0.5 < float(metrics["load_balance_loss"]) < 2.0
    # sparse activation: active < total params
    assert mixtral.active_params_per_token(cfg, params) < mixtral.num_params(
        params
    )


def test_mixtral_train_step_reduces_loss():
    import jax
    import optax

    from ray_tpu.models import mixtral

    cfg = mixtral.MixtralConfig.tiny(vocab_size=64)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-3)
    opt_state = opt.init(params)
    step = jax.jit(mixtral.make_train_step(cfg, opt))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 64)
    first = None
    for i in range(30):
        params, opt_state, m = step(params, opt_state, tokens)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first - 0.5, (first, float(m["loss"]))


def test_mixtral_ep_mesh_matches_local():
    """Expert-parallel forward over the ep axis must match the
    single-device dense-dispatch path."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.models import mixtral

    import dataclasses

    # capacity high enough that NO token drops: dropping is shard-local
    # (per-device capacity), so only the drop-free regime is exactly
    # comparable across layouts
    cfg = dataclasses.replace(
        mixtral.MixtralConfig.tiny(), capacity_factor=16.0
    )
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    local_logits, _ = mixtral.forward(cfg, params, tokens)

    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("ep",))
    ep_logits, _ = mixtral.forward(cfg, params, tokens, mesh)
    np.testing.assert_allclose(
        np.asarray(local_logits), np.asarray(ep_logits), atol=2e-2
    )


def test_gpt2_remat_agrees_with_no_remat():
    """The checkpointed block computes the same loss and gradients as
    the plain one: it only trades memory for recompute.  f32 compute:
    bf16 would add save-vs-recompute rounding noise that has nothing to
    do with the checkpoint's correctness."""
    base = dict(vocab_size=128, n_positions=32, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 128,
                                dtype=jnp.int32)
    ref = None
    for kwargs in ({"remat": False}, {}):
        cfg = gpt2.GPT2Config(**base, **kwargs)
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        loss, grads = jax.value_and_grad(
            lambda p: gpt2.loss_fn(cfg, p, tokens)
        )(params)
        if ref is None:
            ref = (float(loss), grads)
        else:
            assert abs(float(loss) - ref[0]) < 1e-4, kwargs
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                    err_msg=str(kwargs),
                ),
                grads, ref[1],
            )
