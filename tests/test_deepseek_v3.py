"""`models/deepseek_v3.py` (latent attention, dropless experts) on its
own and behind the engine's model seam, at tiny sizes on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import deepseek_v3 as m
from ray_tpu.models import llama
from ray_tpu.parallel import moe
from ray_tpu.serve import engine_model
from ray_tpu.serve.kv_cache import BlockPool
from ray_tpu.serve.llm_engine import LlamaEngine


def _params(cfg, seed=0, std=0.2):
    p = m.init_params(cfg, jax.random.PRNGKey(seed), std=std)
    shape = p["moe_layers"]["router_bias"].shape
    p["moe_layers"]["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 5), shape)
    return p


@pytest.fixture(scope="module")
def model():
    cfg = m.DeepseekV3Config.tiny()
    return cfg, _params(cfg)


def _greedy(cfg, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        lg = m.forward(cfg, params, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(lg[0, -1])))
    return seq[len(prompt):]


def _prompts(n=3):
    return [[int(x) for x in np.random.RandomState(i).randint(1, 256,
                                                              size=5 + 3 * i)]
            for i in range(n)]


# ----------------------------------------------------------------------
# the model's two attention forms
# ----------------------------------------------------------------------
def test_absorbed_decode_equals_expanded_attention(model):
    """Decode scores against the cached latent through `W_uk`, prefill
    expands the latent through `W_kvb`: the same mathematics, so the
    logits agree to float32 rounding (1e-5)."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 1,
                              cfg.vocab_size)
    logits, lat = m.forward(cfg, params, toks, return_kv=True)
    assert lat.shape == (cfg.n_layers, 2, 12, cfg.latent_dim)
    cache = jnp.zeros((cfg.n_layers, 2, 32, 128), jnp.float32)
    cache = cache.at[:, :, :6, :cfg.latent_dim].set(lat[:, :, :6])
    for t in range(6, 12):
        lg, cache, stats = m.decode_step(cfg, params, toks[:, t], cache,
                                         jnp.asarray([t, t]))
        np.testing.assert_allclose(lg, logits[:, t], atol=1e-5, rtol=0)
        assert 0 < int(stats["experts_touched"]) <= \
            cfg.n_moe_layers * cfg.n_routed_experts
    # what the steps wrote is what prefill would have cached
    np.testing.assert_allclose(cache[:, :, :12, :cfg.latent_dim], lat,
                               atol=1e-5)
    assert not np.asarray(cache[..., cfg.latent_dim:]).any()


def test_right_padding_changes_no_real_tokens_result(model):
    """A prompt right-padded to its prefill bucket: causal attention
    and a dropless expert layer leave every real position's logits and
    cached latent as they were (a capacity layer would not: the pad
    rows would compete for slots)."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 11), 1,
                              cfg.vocab_size)
    want, lat = m.forward(cfg, params, toks, return_kv=True)
    padded = jnp.pad(toks, ((0, 0), (0, 5)))
    got, lat_p = m.forward(cfg, params, padded, return_kv=True)
    np.testing.assert_allclose(got[:, :11], want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(lat_p[:, :, :11], lat, atol=1e-5, rtol=0)


def test_dropless_layer_under_one_sided_routing():
    """A bias so uneven that the same six experts get EVERY token: a
    capacity layer drops most of them, this one computes them all, and
    agrees with a per-token loop over the chosen experts."""
    D, I, E, K, N = 32, 16, 16, 6, 40
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    layer = {
        "router": jax.random.normal(ks[0], (D, E)) * 0.02,
        "router_bias": jnp.where(jnp.arange(E) < K, 5.0, 0.0),
        "e_gate": jax.random.normal(ks[1], (E, D, I)) * 0.3,
        "e_up": jax.random.normal(ks[2], (E, D, I)) * 0.3,
        "e_down": jax.random.normal(ks[3], (E, I, D)) * 0.3,
    }
    h = jax.random.normal(ks[4], (N, D))
    y, stats = moe.dropless_moe(h, layer, top_k=K, scale=2.448,
                                route_eps=1e-20, dtype=jnp.float32)
    assert int(stats["experts_touched"]) == K
    assert int(stats["load_max"]) == N       # every token, no capacity
    w, idx = moe.sigmoid_topk_route(h, layer["router"], layer["router_bias"],
                                    K, 2.448, 1e-20)
    assert set(np.asarray(idx).ravel()) == set(range(K))
    # weights come from the scores WITHOUT the bias, normalised, scaled
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.448, rtol=1e-5)
    want = np.zeros((N, D), np.float32)
    for t in range(N):
        for j in range(K):
            e = int(idx[t, j])
            a = jax.nn.silu(h[t] @ layer["e_gate"][e]) * (
                h[t] @ layer["e_up"][e])
            want[t] += float(w[t, j]) * np.asarray(a @ layer["e_down"][e])
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)



@pytest.mark.parametrize("dead", [(), (0, 3, 4, 39), tuple(range(40))],
                         ids=["none", "some", "all"])
def test_masked_rows_are_routed_nowhere_and_counted_nowhere(dead):
    """`dropless_moe(row_mask=...)`, the serve engine's dead decode
    rows: the other rows' results are bit-equal to the unmasked
    layer's, the masked rows' are zeros, and `experts_touched` /
    `load_max` are those of the live rows alone.  The masked rows carry
    one and the same input, as idle slots do (token 0), which unmasked
    piles them onto one set of experts."""
    D, I, E, K, N = 32, 16, 16, 6, 40
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    layer = {
        "router": jax.random.normal(ks[0], (D, E)),
        "router_bias": jnp.zeros((E,)),
        "e_gate": jax.random.normal(ks[1], (E, D, I)) * 0.3,
        "e_up": jax.random.normal(ks[2], (E, D, I)) * 0.3,
        "e_down": jax.random.normal(ks[3], (E, I, D)) * 0.3,
    }
    h = jax.random.normal(ks[4], (N, D))
    mask = np.ones(N, bool)
    mask[list(dead)] = False
    h = jnp.where(jnp.asarray(mask)[:, None], h, h[0])
    kw = dict(top_k=K, scale=2.448, route_eps=1e-20, dtype=jnp.float32)
    y_all, _ = moe.dropless_moe(h, layer, **kw)
    y, stats = moe.dropless_moe(h, layer, row_mask=jnp.asarray(mask), **kw)
    np.testing.assert_array_equal(np.asarray(y)[mask], np.asarray(y_all)[mask])
    assert not np.asarray(y)[~mask].any()
    if mask.any():
        _, alone = moe.dropless_moe(h[np.flatnonzero(mask)], layer, **kw)
        want = (int(alone["experts_touched"]), int(alone["load_max"]))
    else:
        want = (0, 0)
    assert (int(stats["experts_touched"]), int(stats["load_max"])) == want


# ----------------------------------------------------------------------
# behind the seam
# ----------------------------------------------------------------------
def test_the_seam_picks_the_model_by_its_config(model):
    cfg, _ = model
    kw = dict(chunk=2, block_size=8, kv_dtype="model", paged=False,
              interpret=False)
    assert isinstance(engine_model.engine_model_for(cfg, **kw),
                      engine_model.LatentMoeEngineModel)
    assert isinstance(
        engine_model.engine_model_for(llama.LlamaConfig.tiny(), **kw),
        engine_model.LlamaEngineModel)
    with pytest.raises(ValueError, match="latent pool has no heads"):
        engine_model.engine_model_for(cfg, **{**kw, "kv_dtype": "int8"})


def test_cache_spec_at_the_cells_sizes():
    """kanana-2-30b-a3b cut to 7 layers: ONE pool, 576 values a token
    and layer (lane-padded to 640 on the device), 7 x 1,152 B a token
    against Mistral-7B's 16 x 4,096."""
    cfg = m.DeepseekV3Config(n_layers=7)
    kw = dict(chunk=8, block_size=16, kv_dtype="model", paged=True,
              interpret=False)
    model = engine_model.engine_model_for(cfg, **kw)
    pool = BlockPool(9216 + 1, spec=model.cache_leaves)
    assert pool.leaf_shapes(7, 16) == [((7, 9217, 16, 640), jnp.bfloat16)]
    assert model.cache_leaves[0].used == 576
    assert pool.bytes_per_token(7) == 7 * 1152
    mistral = llama.LlamaConfig(dim=4096, n_layers=16, n_heads=32,
                                n_kv_heads=8)
    lm = engine_model.engine_model_for(mistral, **kw)
    assert BlockPool(8, spec=lm.cache_leaves).bytes_per_token(16) \
        == 16 * 4096
    assert len(lm.cache_leaves) == 2 and lm.aux_rows == 0


@pytest.mark.parametrize("kw", [
    dict(decode_kernel="gather"),
    dict(decode_kernel="pallas", kernel_interpret=True),
    dict(decode_kernel="gather", prefix_cache=False),
], ids=["gather", "latent-kernels", "no-prefix-cache"])
def test_engine_serves_the_model_greedy_and_cache_on_equals_off(model, kw):
    """Through `submit`: the latent pool, the absorbed decode (XLA on a
    gathered view, or the Pallas kernels on the pool in place), the
    prefix cache expanding cached latents through `W_kvb`.  Same tokens
    as a greedy loop over the model's full forward, on every route."""
    cfg, params = model
    prompts = _prompts()
    eng = LlamaEngine(cfg, params, slots=2, chunk=2, block_size=8,
                      max_len=48, **kw)
    try:
        assert eng._cache[0].shape == (cfg.n_layers, eng._pool.num_blocks,
                                       8, 128) and len(eng._cache) == 1
        got = [f.result(timeout=300)
               for f in [eng.submit(p, 6) for p in prompts]]
        assert got == [_greedy(cfg, params, p, 6) for p in prompts]
        # the same prompts again, longer: prefix hits where the cache is on
        more = [p + [7, 8, 9] for p in prompts]
        got = [f.result(timeout=300) for f in [eng.submit(p, 4) for p in more]]
        assert got == [_greedy(cfg, params, p, 4) for p in more]
        s = eng.stats()
        assert (s["prefix_hit_tokens"] > 0) == kw.get("prefix_cache", True)
        assert s["cache_bytes_per_token"] == cfg.n_layers * cfg.latent_dim * 4
        assert s["kv_scale_bytes"] == 0
        ticks = [t for t in s["tick_ring"] if "experts_touched" in t]
        assert ticks, "no tick carried the expert counters"
        total = cfg.n_moe_layers * cfg.n_routed_experts
        for t in ticks:
            assert t["experts_total"] == total
            assert 0 <= t["experts_touched"] <= total
            assert 0 <= t["expert_load_max"] <= eng.slots * cfg.top_k
            # dead rows route nowhere: a chunk with no live row (the
            # one in flight when the last request's harvest lags)
            # touches no expert
            assert (t["experts_touched"] > 0) == (t["expert_load_max"] > 0)
        assert any(t["experts_touched"] > 0 for t in ticks)
    finally:
        eng.shutdown()


def test_llama_ticks_carry_no_expert_fields():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = LlamaEngine(cfg, params, slots=2, chunk=2, block_size=8, max_len=32)
    try:
        eng.submit([1, 2, 3], 4).result(timeout=300)
        s = eng.stats()
        assert all("experts_touched" not in t for t in s["tick_ring"])
        assert s["cache_bytes_per_token"] == (
            2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2)
    finally:
        eng.shutdown()
