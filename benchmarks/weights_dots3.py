"""Seeded weights for the dots3-note configuration, made by the benchmark
on the device in the type they are run in (as `weights_deepseek_v3.py`
makes kanana's).  The tree has the layout `ray_tpu.models.dots3` reads —
`layers`, one dict a layer — and that layout is the only thing taken
from the program.  Every leaf of layer `l` comes from
`fold_in(fold_in(key, l), i)` with `i` the leaf's place in `LEAVES`, so
the plain reference makes layer `l` again from the seed alone (`layer`)
and never needs the tree the system holds.

The model dict is the configuration file's `model` (the published
keys; `n_routed_experts` is the experts this chip HOLDS) with the
file's `deployment` beside it: `router_experts` the router's published
width, `expert_offset` the first held expert.

Distributions: N(0, `initializer_range`) for every matrix, the router's
and the correction bias too; norm gains 1, the indexer's LayerNorm bias
0.  The router and its bias are float32 whatever `dtype` says.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import _normal, seed_key

FULL, SWA = "full_attention", "sliding_attention"
F32_LEAVES = ("router", "router_bias")
ATTN = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
        "w_gate_attn", "wo")
INDEX = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_ww")
DENSE = ("mlp_norm", "w_gate", "w_up", "w_down")
MOE = ("mlp_norm", "router", "router_bias", "e_gate", "e_up", "e_down",
       "s_gate", "s_up", "s_down")
# a leaf's place: one list for every kind of layer, so that a leaf's
# key does not depend on what else the layer holds
LEAVES = ATTN + INDEX + DENSE + MOE[1:]


def kind_of(m: dict, layer: int) -> tuple:
    """(attention form, second half) of layer `layer`."""
    return (m["layer_types"][layer],
            "dense" if layer < m["first_k_dense_replace"] else "moe")


def shapes(m: dict, dep: dict, kind: tuple) -> dict:
    p = "" if kind[0] == FULL else "swa_"
    D, H = m["hidden_size"], m[p + "num_attention_heads"]
    nope, rope, v = (m[p + "qk_nope_head_dim"], m[p + "qk_rope_head_dim"],
                     m[p + "v_head_dim"])
    qr, r = m[p + "q_lora_rank"], m[p + "kv_lora_rank"]
    out = {
        "attn_norm": (D,), "wq_a": (D, qr), "q_norm": (qr,),
        "wq_b": (qr, H * (nope + rope)), "wkv_a": (D, r + rope),
        "kv_norm": (r,), "wkv_b": (r, H * (nope + v)),
        "w_gate_attn": (D, H), "wo": (H * v, D), "mlp_norm": (D,),
    }
    if kind[0] == FULL:
        Hi, di = m["index_n_heads"], m["index_head_dim"]
        out.update({"idx_wq": (qr, Hi * di), "idx_wk": (D, di),
                    "idx_k_norm": (di,), "idx_k_bias": (di,),
                    "idx_ww": (D, Hi)})
    if kind[1] == "dense":
        I = m["intermediate_size"]
        out.update({"w_gate": (D, I), "w_up": (D, I), "w_down": (I, D)})
    else:
        E, Eh, Im = (dep["router_experts"], m["n_routed_experts"],
                     m["moe_intermediate_size"])
        Is = m["n_shared_experts"] * Im
        out.update({"router": (D, E), "router_bias": (E,),
                    "e_gate": (Eh, D, Im), "e_up": (Eh, D, Im),
                    "e_down": (Eh, Im, D), "s_gate": (D, Is),
                    "s_up": (D, Is), "s_down": (Is, D)})
    return out


def _layer(key, layer, shp, std, dtype):
    lk = jax.random.fold_in(key, layer)
    out = {}
    for name, shape in shp.items():
        dt = jnp.float32 if name in F32_LEAVES else dtype
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dt)
        elif name == "idx_k_bias":
            out[name] = jnp.zeros(shape, dt)
        else:
            out[name] = _normal(jax.random.fold_in(lk, LEAVES.index(name)),
                                shape, std, dt)
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(shape_items, std, dtype):
    shp = dict(shape_items)
    return jax.jit(lambda key, l: _layer(key, l, shp, std, dtype))


def layer(model: dict, dep: dict, seed: int, layer: int,
          dtype=jnp.bfloat16, std: float = 0.02) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in)."""
    shp = shapes(model, dep, kind_of(model, layer))
    fn = _layer_fn(tuple(sorted(shp.items())), std, dtype)
    return fn(seed_key(seed), jnp.asarray(layer, jnp.int32))


def ends(model: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """Embedding, final norm and (untied) head from the seed alone: this
    chip's slice of the vocabulary."""
    D, V = model["hidden_size"], model["vocab_size"]

    @jax.jit
    def make(key):
        return {"tok_emb": _normal(jax.random.fold_in(key, 10_001), (V, D),
                                   std, dtype),
                "final_norm": jnp.ones((D,), dtype),
                "lm_head": _normal(jax.random.fold_in(key, 10_002), (D, V),
                                   std, dtype)}
    return make(seed_key(seed))


def params(model: dict, dep: dict, seed: int, dtype=jnp.bfloat16,
           std: float = 0.02) -> dict:
    """The whole tree, a layer a jitted call."""
    return {**ends(model, seed, dtype, std),
            "layers": [layer(model, dep, seed, l, dtype, std)
                       for l in range(model["num_hidden_layers"])]}
