"""Seeded weights for the Nemotron 3 Super configuration, made by the
benchmark on the device in the type they are run in (as
`weights_mimo_v2.py` makes MiMo's).  The tree has the layout
`ray_tpu.models.nemotron_h` reads — `layers`, one dict a layer — and that
layout is the only thing taken from the program.  Every leaf of layer
`l` comes from `fold_in(fold_in(key, l), i)` with `i` the leaf's place in
`LEAVES`, so the plain reference makes layer `l` again from the seed
alone (`layer`) and never needs the tree the system holds.

The model dict is the configuration file's `model` (the published keys;
`n_routed_experts` is the experts this chip HOLDS) with the file's
`deployment` beside it: `router_experts` the router's published width.

Distributions (`assumed` in the configuration): N(0,
`initializer_range`) for every matrix, the router's and the correction
bias too; `out_proj` of a Mamba layer N(0, `initializer_range` /
sqrt(published layers)) (`rescale_prenorm_residual`); Mamba-2's usual
initialisation for the rest of a Mamba layer: `dt_bias` the inverse
softplus of a log-uniform draw in [`time_step_min`, `time_step_max`]
floored at `time_step_floor`, `A_log = log U[1, 16]`, `D = 1`, the
convolution's taps and bias U(-1 / sqrt(taps), 1 / sqrt(taps)) (a
depthwise `Conv1d`'s default); norm gains 1.  The router, its bias,
`dt_bias`, `A_log` and `D` are float32 whatever `dtype` says.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import _normal, seed_key

MAMBA, ATTN, MOE = "M", "*", "E"
F32_LEAVES = ("router", "router_bias", "dt_bias", "A_log", "D")
# a leaf's place: one list for every kind of layer, so that a leaf's key
# does not depend on what else the layer holds
LEAVES = ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
          "gate_norm", "out_proj", "wqkv", "wo", "router", "router_bias",
          "w_in", "e_up", "e_down", "w_out", "s_up", "s_down")


def kind_of(m: dict, layer: int) -> str:
    return m["hybrid_override_pattern"][layer]


def shapes(m: dict, dep: dict, kind: str) -> dict:
    D = m["hidden_size"]
    out = {"norm": (D,)}
    if kind == MAMBA:
        H, P = m["mamba_num_heads"], m["mamba_head_dim"]
        di = H * P
        cd = di + 2 * m["n_groups"] * m["ssm_state_size"]
        out.update({"in_proj": (D, di + cd + H),
                    "conv_w": (m["conv_kernel"], cd), "conv_b": (cd,),
                    "dt_bias": (H,), "A_log": (H,), "D": (H,),
                    "gate_norm": (di,), "out_proj": (di, D)})
    elif kind == ATTN:
        H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
        out.update({"wqkv": (D, (H + 2 * KV) * hd), "wo": (H * hd, D)})
    else:
        E, Eh, Z = (dep["router_experts"], m["n_routed_experts"],
                    m["moe_latent_size"])
        Im, Is = (m["moe_intermediate_size"],
                  m["moe_shared_expert_intermediate_size"])
        out.update({"router": (D, E), "router_bias": (E,), "w_in": (D, Z),
                    "e_up": (Eh, Z, Im), "e_down": (Eh, Im, Z),
                    "w_out": (Z, D), "s_up": (D, Is), "s_down": (Is, D)})
    return out


def _layer(key, layer, shp, std, out_std, steps, dtype):
    lk = jax.random.fold_in(key, layer)
    t_min, t_max, t_floor = steps
    out = {}
    for name, shape in shp.items():
        k = jax.random.fold_in(lk, LEAVES.index(name))
        dt = jnp.float32 if name in F32_LEAVES else dtype
        if name.endswith("norm") or name == "D":
            out[name] = jnp.ones(shape, dt)
        elif name == "dt_bias":
            step = jnp.maximum(jnp.exp(
                jax.random.uniform(k, shape, jnp.float32)
                * (math.log(t_max) - math.log(t_min)) + math.log(t_min)),
                t_floor)
            out[name] = step + jnp.log(-jnp.expm1(-step))
        elif name == "A_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                   1.0, 16.0))
        elif name in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(shp["conv_w"][0])
            out[name] = jax.random.uniform(k, shape, jnp.float32, -bound,
                                           bound).astype(dt)
        else:
            out[name] = _normal(k, shape,
                                out_std if name == "out_proj" else std, dt)
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(shape_items, std, out_std, steps, dtype):
    shp = dict(shape_items)
    return jax.jit(lambda key, l: _layer(key, l, shp, std, out_std, steps,
                                         dtype))


def layer(model: dict, dep: dict, seed: int, layer: int,
          dtype=jnp.bfloat16, std: float = 0.02,
          out_std: float = 0.02) -> dict:
    """Layer `layer`'s weights from the seed alone (the reference's way
    in)."""
    shp = shapes(model, dep, kind_of(model, layer))
    steps = (float(model["time_step_min"]), float(model["time_step_max"]),
             float(model["time_step_floor"]))
    fn = _layer_fn(tuple(sorted(shp.items())), std, out_std, steps, dtype)
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
           std: float = 0.02, out_std: float = 0.02) -> dict:
    """The whole tree, a layer a jitted call."""
    return {**ends(model, seed, dtype, std),
            "layers": [layer(model, dep, seed, l, dtype, std, out_std)
                       for l in range(model["num_hidden_layers"])]}
