"""Seeded weights for the `afmoe` configuration, made by the benchmark
on the device in the layout `ray_tpu.models.afmoe` reads (the layout is
the only thing taken from the program): float32 masters, N(0,
`initializer_range`) for every matrix (`assumed` in the configuration's
file), norm weights 1.  Every leaf comes from `fold_in(fold_in(key,
layer), name's index)`, so a leaf can be made again from the seed
alone.  The router's bias starts at zeros (`zero_bias`); `check_bias`
is a second seeded draw of a NON-zero bias for the CPU tests'
comparison with the reference, so that the pick by `s + b` and the
weight by `s` are told apart there (the chip's comparison uses the
balanced bias its window starts from, non-zero too: the plane)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

NORMS = ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm",
         "q_norm", "k_norm")
ATTENTION = ("wq", "wk", "wv", "w_gate_attn", "wo")
DENSE = ("w_gate", "w_up", "w_down")
EXPERTS = ("router", "s_gate", "s_up", "s_down", "e_gate", "e_up", "e_down")


def layer_shapes(m: dict, held: int, dense: bool) -> dict:
    D, H, KV, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    shapes = {"in_norm": (D,), "post_attn_norm": (D,), "pre_mlp_norm": (D,),
              "post_mlp_norm": (D,), "q_norm": (hd,), "k_norm": (hd,),
              "wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd),
              "w_gate_attn": (D, H * hd), "wo": (H * hd, D)}
    if dense:
        I = m["intermediate_size"]
        shapes.update(w_gate=(D, I), w_up=(D, I), w_down=(I, D))
    else:
        I = m["moe_intermediate_size"]
        S = I * m["num_shared_experts"]
        shapes.update(router=(D, m["num_experts"]), s_gate=(D, S),
                      s_up=(D, S), s_down=(S, D), e_gate=(held, D, I),
                      e_up=(held, D, I), e_down=(held, I, D))
    return shapes


def params(m: dict, held: int, rows: int, seed: int, std: float) -> dict:
    """`m`: the configuration's `model`; `held` experts a layer and
    `rows` of the vocabulary are what this chip holds."""
    names = NORMS + ATTENTION + DENSE + EXPERTS
    f32 = jnp.float32

    def layer(key, l):
        lk = jax.random.fold_in(key, l)
        shapes = layer_shapes(m, held, l < m["num_dense_layers"])
        return {n: jnp.ones(s, f32) if n in NORMS else
                jax.random.normal(jax.random.fold_in(lk, names.index(n)),
                                  s, f32) * std
                for n, s in shapes.items()}

    @jax.jit
    def make(key):
        D = m["hidden_size"]
        return {
            "embed": jax.random.normal(jax.random.fold_in(key, 10_001),
                                       (rows, D), f32) * std,
            "layers": [layer(key, l) for l in range(m["num_hidden_layers"])],
            "norm": jnp.ones((D,), f32),
            "head": jax.random.normal(jax.random.fold_in(key, 10_002),
                                      (D, rows), f32) * std,
        }

    return make(seed_key(seed))


def zero_bias(m: dict):
    return jnp.zeros((m["num_hidden_layers"] - m["num_dense_layers"],
                      m["num_experts"]), jnp.float32)


def check_bias(m: dict, seed: int, spread: float = 0.05):
    """N(0, `spread`) a (layer, expert): of the size sigmoid scores
    differ by between neighbours in the ranking, so the bias changes
    which experts are picked and the weights show whether it leaked
    into them."""
    key = jax.random.fold_in(seed_key(seed), 20_001)
    return jax.random.normal(key, zero_bias(m).shape, jnp.float32) * spread
