"""Seeded weights for the MiMo-V2.5 configuration, made by the benchmark
on the device in the type they are run in (as `weights_dots3.py` makes
dots3's).  The tree has the layout `ray_tpu.models.mimo_v2` reads —
`layers`, one dict a layer — and that layout is the only thing taken
from the program.  Every leaf of layer `l` comes from
`fold_in(fold_in(key, l), i)` with `i` the leaf's place in `LEAVES`, so
the plain reference makes layer `l` again from the seed alone (`layer`)
and never needs the tree the system holds.

The model dict is the configuration file's `model` (the published keys;
`n_routed_experts` is the experts this chip HOLDS) with the file's
`deployment` beside it: `router_experts` the router's published width.

Distributions (`assumed` in the configuration): N(0,
`initializer_range`) for every matrix, the router's and the correction
bias too; the window layers' sinks N(0, `sink_std`): a sink of 0 would
be one more key of score 0, and a control that leaves the column out
has to move something; norm gains 1.  The router, its bias and the
sinks are float32 whatever `dtype` says.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import _normal, seed_key

FULL, SWA = 0, 1
F32_LEAVES = ("router", "router_bias", "sink")
# a leaf's place: one list for every kind of layer, so that a leaf's key
# does not depend on what else the layer holds
LEAVES = ("attn_norm", "wqkv", "sink", "wo", "mlp_norm", "w_gate", "w_up",
          "w_down", "router", "router_bias", "e_gate", "e_up", "e_down")


def kind_of(m: dict, layer: int) -> tuple:
    """(attention kind, whether the second half is experts) of a layer."""
    return (m["hybrid_layer_pattern"][layer], m["moe_layer_freq"][layer])


def shapes(m: dict, dep: dict, kind: tuple) -> dict:
    p = "" if kind[0] == FULL else "swa_"
    D, H, KV = (m["hidden_size"], m[p + "num_attention_heads"],
                m[p + "num_key_value_heads"])
    dk, dv = m[p + "head_dim"], m[p + "v_head_dim"]
    out = {"attn_norm": (D,), "wqkv": (D, H * dk + KV * (dk + dv)),
           "wo": (H * dv, D), "mlp_norm": (D,)}
    if m["add_swa_attention_sink_bias" if kind[0] == SWA
         else "add_full_attention_sink_bias"]:
        out["sink"] = (H,)
    if kind[1]:
        E, Eh, Im = (dep["router_experts"], m["n_routed_experts"],
                     m["moe_intermediate_size"])
        out.update({"router": (D, E), "router_bias": (E,),
                    "e_gate": (Eh, D, Im), "e_up": (Eh, D, Im),
                    "e_down": (Eh, Im, D)})
    else:
        I = m["intermediate_size"]
        out.update({"w_gate": (D, I), "w_up": (D, I), "w_down": (I, D)})
    return out


def _layer(key, layer, shp, std, sink_std, dtype):
    lk = jax.random.fold_in(key, layer)
    out = {}
    for name, shape in shp.items():
        dt = jnp.float32 if name in F32_LEAVES else dtype
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dt)
        else:
            out[name] = _normal(jax.random.fold_in(lk, LEAVES.index(name)),
                                shape, sink_std if name == "sink" else std,
                                dt)
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(shape_items, std, sink_std, dtype):
    shp = dict(shape_items)
    return jax.jit(lambda key, l: _layer(key, l, shp, std, sink_std, dtype))


def layer(model: dict, dep: dict, seed: int, layer: int,
          dtype=jnp.bfloat16, std: float = 0.02,
          sink_std: float = 1.0) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in)."""
    shp = shapes(model, dep, kind_of(model, layer))
    fn = _layer_fn(tuple(sorted(shp.items())), std, sink_std, dtype)
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
           std: float = 0.02, sink_std: float = 1.0) -> dict:
    """The whole tree, a layer a jitted call."""
    return {**ends(model, seed, dtype, std),
            "layers": [layer(model, dep, seed, l, dtype, std, sink_std)
                       for l in range(model["num_hidden_layers"])]}
