"""Seeded weights, made by the benchmark — not by the program — on the
device, in one jitted call, in the type they are run in.  The trees
have the layout `ray_tpu.models.{llama,gpt2}` read (the layout is the
only thing taken from the program).  Each stacked leaf is generated
layer by layer from `fold_in(key, layer)`, so the plain reference can
make layer `l` again from the seed alone (`llama_layer`) and never
needs the tree the system holds.

Distributions: N(0, 0.02) for every matrix — Mistral's published
`initializer_range`; GPT-2's paper value, with its residual
projections scaled by 1/sqrt(2 * n_layer) and N(0, 0.01) positions —
norm gains 1, biases 0.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02
LLAMA_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def seed_key(seed: int):
    """Any whole number, also past 2**31: the low 31 bits make the key,
    the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ----------------------------------------------------------------------
# Llama-lineage decoder (Mistral): bf16 as served
# ----------------------------------------------------------------------
def _llama_shapes(m: dict) -> dict:
    E, hd = m["hidden_size"], m["head_dim"]
    H, KV, I = (m["num_attention_heads"], m["num_key_value_heads"],
                m["intermediate_size"])
    return {"wq": (E, H * hd), "wk": (E, KV * hd), "wv": (E, KV * hd),
            "wo": (H * hd, E), "w_gate": (E, I), "w_up": (E, I),
            "w_down": (I, E)}


def _llama_layer(key, layer, shapes, dtype):
    lk = jax.random.fold_in(key, layer)
    E = shapes["wq"][0]
    out = {name: _normal(jax.random.fold_in(lk, i), shapes[name], STD, dtype)
           for i, name in enumerate(LLAMA_MATRICES)}
    out["attn_norm"] = jnp.ones((E,), dtype)
    out["mlp_norm"] = jnp.ones((E,), dtype)
    return out


@functools.lru_cache(maxsize=None)
def _llama_layer_fn(shapes_items, dtype):
    shapes = dict(shapes_items)
    return jax.jit(lambda key, l: _llama_layer(key, l, shapes, dtype))


def llama_layer(model: dict, seed: int, layer: int, dtype=jnp.bfloat16) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in)."""
    shapes = _llama_shapes(model)
    return _llama_layer_fn(tuple(sorted(shapes.items())), dtype)(
        seed_key(seed), jnp.asarray(layer, jnp.int32))


def llama_ends(model: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and (untied) head from the seed alone."""
    E, V = model["hidden_size"], model["vocab_size"]

    @jax.jit
    def make(key):
        return {"tok_emb": _normal(jax.random.fold_in(key, 10_001), (V, E),
                                   STD, dtype),
                "final_norm": jnp.ones((E,), dtype),
                "lm_head": _normal(jax.random.fold_in(key, 10_002), (E, V),
                                   STD, dtype)}
    return make(seed_key(seed))


def llama_params(model: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole tree in one jitted call; `lax.map` over the layers
    keeps the generator's temporaries to one layer's worth."""
    shapes = _llama_shapes(model)
    L = model["num_hidden_layers"]

    @jax.jit
    def make(key):
        blocks = jax.lax.map(
            lambda l: _llama_layer(key, l, shapes, dtype),
            jnp.arange(L, dtype=jnp.int32))
        return blocks

    return {**llama_ends(model, seed, dtype), "blocks": make(seed_key(seed))}


# ----------------------------------------------------------------------
# GPT-2: f32 masters
# ----------------------------------------------------------------------
def gpt2_params(model: dict, seed: int) -> dict:
    E, L, V, P = (model["n_embd"], model["n_layer"], model["vocab_size"],
                  model["n_positions"])
    proj = STD / math.sqrt(2 * L)
    f32 = jnp.float32

    def layer(key, l):
        lk = jax.random.fold_in(key, l)
        k = [jax.random.fold_in(lk, i) for i in range(4)]
        return {
            "ln1_g": jnp.ones((E,), f32), "ln1_b": jnp.zeros((E,), f32),
            "attn_qkv_w": _normal(k[0], (E, 3 * E), STD, f32),
            "attn_qkv_b": jnp.zeros((3 * E,), f32),
            "attn_out_w": _normal(k[1], (E, E), proj, f32),
            "attn_out_b": jnp.zeros((E,), f32),
            "ln2_g": jnp.ones((E,), f32), "ln2_b": jnp.zeros((E,), f32),
            "mlp_fc_w": _normal(k[2], (E, 4 * E), STD, f32),
            "mlp_fc_b": jnp.zeros((4 * E,), f32),
            "mlp_out_w": _normal(k[3], (4 * E, E), proj, f32),
            "mlp_out_b": jnp.zeros((E,), f32),
        }

    @jax.jit
    def make(key):
        return {
            "wte": _normal(jax.random.fold_in(key, 10_001), (V, E), STD, f32),
            "wpe": _normal(jax.random.fold_in(key, 10_002), (P, E), 0.01, f32),
            "blocks": jax.lax.map(lambda l: layer(key, l),
                                  jnp.arange(L, dtype=jnp.int32)),
            "lnf_g": jnp.ones((E,), f32), "lnf_b": jnp.zeros((E,), f32),
        }

    return make(seed_key(seed))
