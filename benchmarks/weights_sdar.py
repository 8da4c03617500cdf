"""Seeded weights for the SDAR-30B-A3B configuration, made by the
benchmark on the device in the type they are run in (as `weights_lfm2.py`
makes the hybrid's).  The tree has the layout `ray_tpu.models.sdar`
reads — `tok_emb`, `final_norm`, `lm_head` and `layers`, each leaf
`[L, ...]` — and that layout is the only thing taken from the program.
Every leaf of layer `l` comes from `fold_in(fold_in(key, l), i)` with `i`
the leaf's place in `LEAVES`, so the plain reference makes layer `l`
again from the seed alone (`layer`) and never needs the tree the system
holds.

Distributions (`assumed` in the configuration): N(0,
`initializer_range`) for every matrix, the router's too; norm gains 1
(the layer norms and the per-head q and k norms).  The router is float32
whatever `dtype` says.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import _normal, seed_key

F32_LEAVES = ("router",)
LEAVES = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
          "mlp_norm", "router", "e_gate", "e_up", "e_down")
# the matrices a lower-precision control rounds (the router, the norms,
# the embedding and the head stay)
MATRICES = ("wq", "wk", "wv", "wo", "e_gate", "e_up", "e_down")


def shapes(m: dict) -> dict:
    D, H, KV, d = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    E, I = m["num_experts"], m["moe_intermediate_size"]
    return {"attn_norm": (D,), "wq": (D, H * d), "wk": (D, KV * d),
            "wv": (D, KV * d), "q_norm": (d,), "k_norm": (d,),
            "wo": (H * d, D), "mlp_norm": (D,), "router": (D, E),
            "e_gate": (E, D, I), "e_up": (E, D, I), "e_down": (E, I, D)}


def _layer(key, layer, shp, std, dtype):
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, name in enumerate(LEAVES):
        dt = jnp.float32 if name in F32_LEAVES else dtype
        out[name] = (jnp.ones(shp[name], dt) if name.endswith("norm")
                     else _normal(jax.random.fold_in(lk, i), shp[name], std,
                                  dt))
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(shape_items, std, dtype):
    shp = dict(shape_items)
    return jax.jit(lambda key, l: _layer(key, l, shp, std, dtype))


def layer(model: dict, seed: int, layer: int, dtype=jnp.bfloat16,
          std: float = 0.02) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in)."""
    fn = _layer_fn(tuple(sorted(shapes(model).items())), float(std), dtype)
    return fn(seed_key(seed), jnp.asarray(layer, jnp.int32))


def ends(model: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """Embedding, final norm and (untied) head from the seed alone."""
    D, V = model["hidden_size"], model["vocab_size"]

    @jax.jit
    def make(key):
        return {"tok_emb": _normal(jax.random.fold_in(key, 10_001), (V, D),
                                   std, dtype),
                "final_norm": jnp.ones((D,), dtype),
                "lm_head": _normal(jax.random.fold_in(key, 10_002), (D, V),
                                   std, dtype)}
    return make(seed_key(seed))


def params(model: dict, seed: int, dtype=jnp.bfloat16,
           std: float = 0.02) -> dict:
    """The whole tree; the layers' stacks in one jitted call whose
    `lax.map` over the layers keeps the generator's temporaries to one
    layer's worth (an expert layer is 1.25 GB in bfloat16 at the
    published widths)."""
    shp = shapes(model)

    @jax.jit
    def stack(key):
        return jax.lax.map(
            lambda l: _layer(key, l, shp, float(std), dtype),
            jnp.arange(model["num_hidden_layers"], dtype=jnp.int32))

    return {**ends(model, seed, dtype, std), "layers": stack(seed_key(seed))}
