"""The system against each plain reference, at tiny sizes on the CPU,
and each control shown to fail the comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import gpt2 as gpt2_ref
from benchmarks.reference import mistral as mistral_ref
from benchmarks.reference import precision

GPT2 = {"n_embd": 64, "n_head": 4, "n_layer": 2, "n_positions": 64,
        "vocab_size": 512}
MISTRAL = {"hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
           "num_hidden_layers": 2, "vocab_size": 256,
           "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
           "rope_theta": 1e6}


def test_references_import_nothing_from_the_program():
    import inspect

    for mod in (gpt2_ref, mistral_ref, precision):
        src = inspect.getsource(mod)
        assert "import ray_tpu" not in src and "from ray_tpu" not in src


def test_weights_same_seed_same_values_and_large_seeds():
    a = weights.gpt2_params(GPT2, 2**31 + 12345)
    b = weights.gpt2_params(GPT2, 2**31 + 12345)
    c = weights.gpt2_params(GPT2, 12345)
    assert all(jnp.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not jnp.array_equal(a["wte"], c["wte"])


def test_llama_layer_from_seed_equals_the_stacked_tree():
    tree = weights.llama_params(MISTRAL, 9, jnp.float32)
    for l in range(MISTRAL["num_hidden_layers"]):
        one = weights.llama_layer(MISTRAL, 9, l, jnp.float32)
        for k, v in one.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(tree["blocks"][k][l]))
    ends = weights.llama_ends(MISTRAL, 9, jnp.float32)
    np.testing.assert_array_equal(np.asarray(ends["lm_head"]),
                                  np.asarray(tree["lm_head"]))


# ---------------------------------------------------------------- GPT-2
def _gpt2_case(seed=3, dtype=jnp.float32):
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config(
        vocab_size=GPT2["vocab_size"], n_positions=GPT2["n_positions"],
        n_embd=GPT2["n_embd"], n_layer=GPT2["n_layer"], n_head=GPT2["n_head"],
        dtype=dtype, attention="dense")
    params = weights.gpt2_params(GPT2, seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, GPT2["vocab_size"], size=(2, 33)).astype(np.int32))
    sys_l, sys_g = jax.value_and_grad(
        lambda p: gpt2.loss_fn(cfg, p, tokens))(params)
    return params, tokens, sys_l, sys_g


def _rel_err(a, b):
    sq = lambda t: sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))  # noqa: E731
                       for x in jax.tree.leaves(t))
    diff = jax.tree.map(lambda x, y: x.astype(jnp.float32) - y, a, b)
    return (sq(diff) / sq(b)) ** 0.5


def test_gpt2_loss_and_gradient_match_the_reference_in_float32():
    params, tokens, sys_l, sys_g = _gpt2_case()
    ref_l, ref_g = gpt2_ref.loss_and_grad(params, tokens, GPT2["n_head"])
    assert abs(float(sys_l) - float(ref_l)) < 1e-5
    assert _rel_err(sys_g, ref_g) < 1e-4


def test_gpt2_bf16_system_passes_and_fp8_control_fails_one_limit():
    """The tolerance rule at test size: the limit sits above what the
    system gives in the stated precision (bfloat16 matmuls) and below
    what the reference gives one step lower (float8 operands)."""
    params, tokens, _, sys_g = _gpt2_case(dtype=jnp.bfloat16)
    _, ref_g = gpt2_ref.loss_and_grad(params, tokens, GPT2["n_head"])
    _, ctl_g = gpt2_ref.loss_and_grad(params, tokens, GPT2["n_head"],
                                      precision.fp8_e4m3)
    sound, control = _rel_err(sys_g, ref_g), _rel_err(ctl_g, ref_g)
    assert sound < 0.02, sound
    assert control > 3 * sound and control > 0.04, (sound, control)


# -------------------------------------------------------------- Mistral
def _reference_margins(model, seed, prompt, served):
    """Teacher-forced margins of `served` below the reference's argmax,
    the reference making its weights from the seed alone."""
    ends = weights.llama_ends(model, seed, jnp.float32)
    full = list(prompt) + list(served)
    x = mistral_ref.embed(jnp.asarray(full[:-1], jnp.int32), ends["tok_emb"])
    for l in range(model["num_hidden_layers"]):
        x = mistral_ref.layer(
            x, weights.llama_layer(model, seed, l, jnp.float32),
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"], rope_theta=model["rope_theta"],
            eps=model["rms_norm_eps"])
    logits = mistral_ref.head(x[len(prompt) - 1:], ends["final_norm"],
                              ends["lm_head"], model["rms_norm_eps"])
    return np.asarray(mistral_ref.margins(
        logits, jnp.asarray(served, jnp.int32)))


def _serve_through_engine(model, seed, prompts, n_new, int8=False):
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LlamaEngine

    cfg = llama.LlamaConfig(
        vocab_size=model["vocab_size"], max_seq_len=256,
        dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        intermediate=model["intermediate_size"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        dtype=jnp.float32)
    params = weights.llama_params(model, seed, jnp.float32)
    if int8:
        params = llama.quantize_weights_int8(params)
    eng = LlamaEngine(cfg, params, slots=4, max_len=64, chunk=2,
                      block_size=8, kv_blocks=64, decode_kernel="gather")
    try:
        return [eng.submit(p, n_new).result(timeout=300) for p in prompts]
    finally:
        eng.shutdown()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(1, MISTRAL["vocab_size"], size=n).tolist()
            for n in (8, 13, 16, 24, 9, 17, 21, 11)]


def test_gqa_prefill_then_decode_through_the_engine_matches_reference(prompts):
    served = _serve_through_engine(MISTRAL, 5, prompts, 12)
    margins = np.concatenate([
        _reference_margins(MISTRAL, 5, p, s) for p, s in zip(prompts, served)])
    assert margins.size == 12 * len(prompts)
    # float32 engine against the float32 reference: the served token is
    # the reference's own choice, up to float32 ties
    assert margins.max() < 1e-4


def test_serve_comparison_fails_under_int8_weights(prompts):
    """The program's own lower-precision path is the control: its mean
    margin has to clear the limit the sound run stays under."""
    sound = np.concatenate([
        _reference_margins(MISTRAL, 5, p, s) for p, s in zip(
            prompts, _serve_through_engine(MISTRAL, 5, prompts, 24))])
    control = np.concatenate([
        _reference_margins(MISTRAL, 5, p, s) for p, s in zip(
            prompts, _serve_through_engine(MISTRAL, 5, prompts, 24, int8=True))])
    limit = 5e-6
    assert sound.mean() < limit, sound.mean()
    assert control.mean() > 3 * limit, control.mean()
