"""Seeded weights for the Brumby configuration, made by the benchmark
on the device in the type they are run in (as `weights.py` makes
Mistral's).  The tree has the layout `ray_tpu.models.brumby` reads, and
that layout is the only thing taken from the program.  Every leaf of
layer `l` comes from `fold_in(fold_in(key, l), i)` with `i` the leaf's
place in `LEAVES`, so the plain reference makes layer `l` again from
the seed alone (`layer`).

Distributions (`assumed` in the configuration): N(0,
`initializer_range`) for every matrix, the gate's projection too; norm
gains 1 (`q_norm` and `k_norm` as well); the gate's bias uniform in
`gate_bias` = [4.6, 6.9] (sigmoid 0.990 .. 0.999, time constants of 100
to 1,000 tokens: at 0 random weights forget in two tokens and `correct`
could not see a broken state path).  The gate's projection and bias are
float32 whatever `dtype` says: its logarithm is summed over a context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import _normal, seed_key

F32_LEAVES = ("wg", "bg")
LEAVES = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wg", "bg",
          "wo", "mlp_norm", "w_gate", "w_up", "w_down")


def shapes(m: dict) -> dict:
    D, d = m["hidden_size"], m["head_dim"]
    H, KV, I = (m["num_attention_heads"], m["num_key_value_heads"],
                m["intermediate_size"])
    return {"attn_norm": (D,), "wq": (D, H * d), "wk": (D, KV * d),
            "wv": (D, KV * d), "q_norm": (d,), "k_norm": (d,),
            "wg": (D, KV), "bg": (KV,), "wo": (H * d, D), "mlp_norm": (D,),
            "w_gate": (D, I), "w_up": (D, I), "w_down": (I, D)}


def _layer(key, layer, shp, std, bias, dtype):
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, name in enumerate(LEAVES):
        dt = jnp.float32 if name in F32_LEAVES else dtype
        k = jax.random.fold_in(lk, i)
        if name.endswith("norm"):
            out[name] = jnp.ones(shp[name], dt)
        elif name == "bg":
            out[name] = jax.random.uniform(k, shp[name], dt, *bias)
        else:
            out[name] = _normal(k, shp[name], std, dt)
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(shape_items, std, bias, dtype):
    shp = dict(shape_items)
    return jax.jit(lambda key, l: _layer(key, l, shp, std, bias, dtype))


def _args(model: dict, assumed: dict):
    return (tuple(sorted(shapes(model).items())),
            float(assumed["initializer_range"]),
            tuple(float(b) for b in assumed["gate_bias"]))


def layer(model: dict, assumed: dict, seed: int, layer: int,
          dtype=jnp.bfloat16) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in)."""
    return _layer_fn(*_args(model, assumed), dtype)(
        seed_key(seed), jnp.asarray(layer, jnp.int32))


def tok_emb(model: dict, assumed: dict, seed: int, dtype=jnp.bfloat16):
    """The embedding alone (1.56 GB in bfloat16 at the published sizes:
    the reference makes it, embeds, and lets it go)."""
    shape = (model["vocab_size"], model["hidden_size"])
    std = float(assumed["initializer_range"])
    return jax.jit(lambda key: _normal(jax.random.fold_in(key, 10_001),
                                       shape, std, dtype))(seed_key(seed))


def head(model: dict, assumed: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Final norm and (untied) head from the seed alone."""
    D, V = model["hidden_size"], model["vocab_size"]
    std = float(assumed["initializer_range"])

    @jax.jit
    def make(key):
        return {"final_norm": jnp.ones((D,), dtype),
                "lm_head": _normal(jax.random.fold_in(key, 10_002), (D, V),
                                   std, dtype)}
    return make(seed_key(seed))


def ends(model: dict, assumed: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and head together (the system's tree)."""
    return {"tok_emb": tok_emb(model, assumed, seed, dtype),
            **head(model, assumed, seed, dtype)}


def params(model: dict, assumed: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole tree; the stack in one jitted call whose `lax.map` over
    its layers keeps the generator's temporaries to one layer's worth."""
    shape_items, std, bias = _args(model, assumed)
    shp = dict(shape_items)

    @jax.jit
    def make(key):
        return jax.lax.map(lambda l: _layer(key, l, shp, std, bias, dtype),
                           jnp.arange(model["num_hidden_layers"],
                                      dtype=jnp.int32))

    return {**ends(model, assumed, seed, dtype), "blocks": make(seed_key(seed))}
