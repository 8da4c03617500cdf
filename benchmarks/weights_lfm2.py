"""Seeded weights for the LFM2-MoE configuration, made by the benchmark
on the device in the type they are run in (as `weights.py` makes
Mistral's).  The tree has the layout `ray_tpu.models.lfm2` reads — four
stacks, `conv` and `attn` (a layer's operator) and `dense` and `moe` (its
second half), and `tok_emb`, which is also the head — and that layout is
the only thing taken from the program.  Every leaf of layer `l` comes
from `fold_in(fold_in(key, l), i)` with `i` the leaf's place in
`LEAVES`, so the plain reference makes layer `l` again from the seed
alone (`layer`) and never needs the tree the system holds.

Distributions (`assumed` in the configuration): N(0,
`initializer_range`) for every matrix, the convolution's taps, the
router and the expert bias too (the bias is a checkpoint buffer, and
zeros would leave its path unrun); norm gains 1 (the q and k head norms
as well).  The router and its bias are float32 whatever `dtype` says:
the published code computes the scores in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import _normal, seed_key

F32_LEAVES = ("router", "router_bias")
# a leaf's place: an operator's and a second half's never collide
LEAVES = {
    "conv": ("op_norm", "w_in", "conv_w", "w_out"),
    "attn": ("op_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"),
    "dense": ("ffn_norm", "w1", "w3", "w2"),
    "moe": ("ffn_norm", "router", "router_bias", "e_gate", "e_up", "e_down"),
}
PLACE = {"conv": 0, "attn": 0, "dense": 16, "moe": 16}


def shapes(m: dict, assumed: dict) -> dict:
    D, d = m["hidden_size"], assumed["head_dim"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    I, Im, E = (m["intermediate_size"], m["moe_intermediate_size"],
                m["num_experts"])
    return {
        "op_norm": (D,), "w_in": (D, 3 * D), "conv_w": (D, m["conv_L_cache"]),
        "w_out": (D, D), "wq": (D, H * d), "wk": (D, KV * d),
        "wv": (D, KV * d), "q_norm": (d,), "k_norm": (d,), "wo": (H * d, D),
        "ffn_norm": (D,), "w1": (D, I), "w3": (D, I), "w2": (I, D),
        "router": (D, E), "router_bias": (E,), "e_gate": (E, D, Im),
        "e_up": (E, D, Im), "e_down": (E, Im, D),
    }


def kinds_of(m: dict, layer: int) -> tuple:
    """(operator stack, second-half stack) of layer `layer`."""
    return ("conv" if m["layer_types"][layer] == "conv" else "attn",
            "dense" if layer < m["num_dense_layers"] else "moe")


def stack_layers(m: dict) -> dict:
    """stack -> the model's layers that lie in it, in order."""
    out = {k: [] for k in LEAVES}
    for l in range(m["num_hidden_layers"]):
        for kind in kinds_of(m, l):
            out[kind].append(l)
    return out


def _part(key, layer, kind, shp, std, dtype):
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, name in enumerate(LEAVES[kind]):
        dt = jnp.float32 if name in F32_LEAVES else dtype
        if name.endswith("norm"):
            out[name] = jnp.ones(shp[name], dt)
        else:
            out[name] = _normal(jax.random.fold_in(lk, PLACE[kind] + i),
                                shp[name], std, dt)
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(kinds, shape_items, std, dtype):
    shp = dict(shape_items)

    def make(key, l):
        out = {}
        for kind in kinds:
            out.update(_part(key, l, kind, shp, std, dtype))
        return out
    return jax.jit(make)


def _args(model: dict, assumed: dict):
    return (tuple(sorted(shapes(model, assumed).items())),
            float(assumed["initializer_range"]))


def layer(model: dict, assumed: dict, seed: int, layer: int,
          dtype=jnp.bfloat16) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in): its operator's leaves and its second half's in one dict."""
    fn = _layer_fn(kinds_of(model, layer), *_args(model, assumed), dtype)
    return fn(seed_key(seed), jnp.asarray(layer, jnp.int32))


def ends(model: dict, assumed: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The embedding (also the head: tied) and the last norm."""
    D, V = model["hidden_size"], model["vocab_size"]
    std = float(assumed["initializer_range"])

    @jax.jit
    def make(key):
        return {"tok_emb": _normal(jax.random.fold_in(key, 10_001), (V, D),
                                   std, dtype),
                "embedding_norm": jnp.ones((D,), dtype)}
    return make(seed_key(seed))


def params(model: dict, assumed: dict, seed: int,
           dtype=jnp.bfloat16) -> dict:
    """The whole tree; each stack in one jitted call whose `lax.map`
    over its layers keeps the generator's temporaries to one layer's
    worth (one expert layer is 0.70 GB in bfloat16 at the published
    widths)."""
    shape_items, std = _args(model, assumed)
    shp = dict(shape_items)

    def stack(kind, layers):
        @jax.jit
        def make(key):
            return jax.lax.map(
                lambda l: _part(key, l, kind, shp, std, dtype),
                jnp.asarray(layers, jnp.int32))
        return make(seed_key(seed))

    return {**ends(model, assumed, seed, dtype),
            **{kind: stack(kind, layers)
               for kind, layers in stack_layers(model).items()}}
