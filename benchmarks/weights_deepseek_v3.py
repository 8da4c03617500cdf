"""Seeded weights for the DeepSeek-V3-lineage configuration, made by
the benchmark on the device in the type they are run in (as
`weights.py` makes Mistral's).  The tree has the layout
`ray_tpu.models.deepseek_v3` reads — two stacks, `dense_layers` and
`moe_layers` — and that layout is the only thing taken from the
program.  Every leaf of layer `l` comes from `fold_in(fold_in(key, l),
i)` with `i` the leaf's place in `LEAVES`, so the plain reference makes
layer `l` again from the seed alone (`layer`) and never needs the tree
the system holds.

Distributions: N(0, `initializer_range`) for every matrix, the router's
and the correction bias too (`assumed` in the configuration: the bias
is a checkpoint buffer, and zeros would leave its path unrun); norm
gains 1.  The router and its bias are float32 whatever `dtype` says:
the published code computes the scores in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import _normal, seed_key

F32_LEAVES = ("router", "router_bias")
ATTN = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "mlp_norm")
LEAVES = {
    "dense": ATTN + ("w_gate", "w_up", "w_down"),
    "moe": ATTN + ("router", "router_bias", "e_gate", "e_up", "e_down",
                   "s_gate", "s_up", "s_down"),
}


def shapes(m: dict) -> dict:
    D, H = m["hidden_size"], m["num_attention_heads"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    r, I, Im, E = (m["kv_lora_rank"], m["intermediate_size"],
                   m["moe_intermediate_size"], m["n_routed_experts"])
    Is = m["n_shared_experts"] * Im
    return {
        "attn_norm": (D,), "wq": (D, H * (nope + rope)),
        "wkv_a": (D, r + rope), "kv_norm": (r,),
        "wkv_b": (r, H * (nope + v)), "wo": (H * v, D), "mlp_norm": (D,),
        "w_gate": (D, I), "w_up": (D, I), "w_down": (I, D),
        "router": (D, E), "router_bias": (E,),
        "e_gate": (E, D, Im), "e_up": (E, D, Im), "e_down": (E, Im, D),
        "s_gate": (D, Is), "s_up": (D, Is), "s_down": (Is, D),
    }


def kind_of(m: dict, layer: int) -> str:
    return "dense" if layer < m["first_k_dense_replace"] else "moe"


def _layer(key, layer, kind, shp, std, dtype):
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, name in enumerate(LEAVES[kind]):
        dt = jnp.float32 if name in F32_LEAVES else dtype
        if name.endswith("norm"):
            out[name] = jnp.ones(shp[name], dt)
        else:
            out[name] = _normal(jax.random.fold_in(lk, i), shp[name], std, dt)
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(kind, shape_items, std, dtype):
    shp = dict(shape_items)
    return jax.jit(lambda key, l: _layer(key, l, kind, shp, std, dtype))


def layer(model: dict, seed: int, layer: int, dtype=jnp.bfloat16,
          std: float = 0.02) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in): a dense layer's leaves or an expert layer's."""
    fn = _layer_fn(kind_of(model, layer),
                   tuple(sorted(shapes(model).items())), std, dtype)
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
    """The whole tree; each stack in one jitted call whose `lax.map`
    over its layers keeps the generator's temporaries to one layer's
    worth (one expert layer is 1.28 GB in bfloat16 at kanana's widths)."""
    shp = shapes(model)
    n_dense = model["first_k_dense_replace"]
    L = model["num_hidden_layers"]

    def stack(kind, lo, hi):
        @jax.jit
        def make(key):
            return jax.lax.map(
                lambda l: _layer(key, l, kind, shp, std, dtype),
                jnp.arange(lo, hi, dtype=jnp.int32))
        return make(seed_key(seed))

    return {**ends(model, seed, dtype, std),
            "dense_layers": stack("dense", 0, n_dense),
            "moe_layers": stack("moe", n_dense, L)}
