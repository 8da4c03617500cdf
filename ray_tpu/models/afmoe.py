"""The `afmoe` family (window and full attention with grouped heads and
an output gate, a dropless share of sigmoid-routed experts beside a
shared one, a balancing bias that a rule moves), as a model that
TRAINS.

What is here is ONE CHIP'S SHARE of the model in an expert-parallel,
vocabulary-parallel deployment, with nothing standing in for the other
chips:

- an expert layer holds `cfg.held = (offset, count)` of the router's
  `num_experts` experts.  The router keeps its published width, the
  top-k is taken over all of them, and a pair whose expert lives on
  another chip adds nothing here (`parallel/moe.dropless_moe_train`:
  no pair of a held expert is dropped whatever the load);
- the embedding and the head hold `cfg.vocab_slice = (offset, rows)`
  of the vocabulary: a token outside the slice embeds to zeros, its
  target's logit is not here, and the log-sum-exp runs over the
  slice's logits.

The router's balancing bias is NOT a parameter: it lives beside them
(`init_router_bias`, the step's `state["router_bias"]`), takes no
gradient, no weight decay and no optimizer moments, and after every
optimizer update moves by `update_router_bias`' rule from the counts
of the step.

Same stance as `models/gpt2.py` / `mixtral.py`: explicit param pytree,
pure functions, float32 parameters with bfloat16 matmul operands.  The
layers differ in kind (a dense SwiGLU layer first, then expert layers;
window layers rotate their heads, full layers use no positions), so
they are a list walked in Python, each a remat block of its own
(`ops.attention.checkpoint_block`: the flash kernel's results kept).
Router scores, the sigmoid, the renormalisation and the log-sum-exp are
float32.

`jax.named_scope`s name the parts in a device trace (`embed`,
`attn_window`, `attn_full`, `dense_mlp`, `moe_router`, `moe_routed`,
`moe_shared`, `lm_head`, and the step's `optimizer` and `router_bias`);
they change nothing that is computed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import checkpoint_block, flash_attention
from ray_tpu.parallel.moe import dropless_moe_train

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    num_dense_layers: int = 2
    intermediate: int = 6144        # the dense layers' SwiGLU
    moe_intermediate: int = 1024    # an expert's, and the shared one's
    num_experts: int = 128          # the router's outputs
    top_k: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.826
    route_eps: float = 1e-20
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    mup_enabled: bool = True
    load_balance_coeff: float = 1e-3
    # this chip's share
    held: Tuple[int, int] = (0, 128)
    vocab_slice: Tuple[int, int] = (0, 200192)
    # how it is computed
    dtype: Any = jnp.bfloat16
    attention: str = "flash"        # flash | dense
    logits_dtype: Any = jnp.float32
    kernel: bool = False            # Pallas grouped products (TPU)
    interpret: bool = False         # the kernels in the interpreter

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.num_dense_layers

    @staticmethod
    def tiny(**kw) -> "AfmoeConfig":
        base = dict(
            vocab_size=512, hidden=64, n_heads=4, n_kv_heads=2, head_dim=16,
            layer_types=(SLIDING, SLIDING, FULL), num_dense_layers=1,
            intermediate=128, moe_intermediate=32, num_experts=8, top_k=2,
            sliding_window=8, held=(0, 4), vocab_slice=(0, 512),
            attention="dense")
        base.update(kw)
        return AfmoeConfig(**base)


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------
def _layer_shapes(cfg: AfmoeConfig, dense: bool) -> Dict:
    D, H, KV, hd = cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "in_norm": (D,), "post_attn_norm": (D,), "pre_mlp_norm": (D,),
        "post_mlp_norm": (D,), "q_norm": (hd,), "k_norm": (hd,),
        "wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd),
        "w_gate_attn": (D, H * hd), "wo": (H * hd, D),
    }
    if dense:
        shapes.update({"w_gate": (D, cfg.intermediate),
                       "w_up": (D, cfg.intermediate),
                       "w_down": (cfg.intermediate, D)})
    else:
        I, S = cfg.moe_intermediate, (cfg.moe_intermediate
                                      * cfg.num_shared_experts)
        shapes.update({
            "router": (D, cfg.num_experts),
            "s_gate": (D, S), "s_up": (D, S), "s_down": (S, D),
            "e_gate": (cfg.held[1], D, I), "e_up": (cfg.held[1], D, I),
            "e_down": (cfg.held[1], I, D)})
    return shapes


_LAYER_AXES = {
    "in_norm": ("embed",), "post_attn_norm": ("embed",),
    "pre_mlp_norm": ("embed",), "post_mlp_norm": ("embed",),
    "q_norm": (None,), "k_norm": (None,),
    "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
    "wv": ("embed", "kv_heads"), "w_gate_attn": ("embed", "heads"),
    "wo": ("heads", "embed"),
    "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
    "router": ("embed", None),
    "s_gate": ("embed", "mlp"), "s_up": ("embed", "mlp"),
    "s_down": ("mlp", "embed"),
    "e_gate": ("expert", "embed", "mlp"), "e_up": ("expert", "embed", "mlp"),
    "e_down": ("expert", "mlp", "embed"),
}


def init_params(cfg: AfmoeConfig, key: jax.Array, std: float = 0.02) -> Dict:
    """N(0, std) matrices, norm weights of ones; `layers` is a list, a
    dense layer's dict first (`num_dense_layers` of them)."""
    rows = cfg.vocab_slice[1]
    keys = iter(jax.random.split(key, 2 + 16 * cfg.n_layers))

    def n(shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    layers = []
    for i in range(cfg.n_layers):
        shapes = _layer_shapes(cfg, i < cfg.num_dense_layers)
        layers.append({k: jnp.ones(s, jnp.float32) if k.endswith("norm")
                       else n(s) for k, s in shapes.items()})
    return {"embed": n((rows, cfg.hidden)), "layers": layers,
            "norm": jnp.ones((cfg.hidden,), jnp.float32),
            "head": n((cfg.hidden, rows))}


def init_router_bias(cfg: AfmoeConfig) -> jax.Array:
    """[expert layers, num_experts] float32 zeros: the state the rule
    moves."""
    return jnp.zeros((cfg.n_moe_layers, cfg.num_experts), jnp.float32)


def logical_axes(cfg: AfmoeConfig) -> Dict:
    return {"embed": ("vocab", "embed"),
            "layers": [{k: _LAYER_AXES[k] for k in _layer_shapes(
                cfg, i < cfg.num_dense_layers)} for i in range(cfg.n_layers)],
            "norm": ("embed",), "head": ("embed", "vocab")}


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


def active_params_per_token(cfg: AfmoeConfig) -> int:
    """Matmul parameters a token meets ON THIS CHIP by expectation: the
    attention, the dense layers, the shared expert, the router, the
    head's slice, and `top_k * held / num_experts` routed experts."""
    D, hd = cfg.hidden, cfg.head_dim
    attn = D * hd * (3 * cfg.n_heads + 2 * cfg.n_kv_heads)
    dense = 3 * D * cfg.intermediate
    expert = 3 * D * cfg.moe_intermediate
    moe = (D * cfg.num_experts + expert * cfg.num_shared_experts
           + expert * cfg.top_k * cfg.held[1] / cfg.num_experts)
    return int(cfg.n_layers * attn + cfg.num_dense_layers * dense
               + cfg.n_moe_layers * moe + D * cfg.vocab_slice[1])


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(ms + eps) * g).astype(x.dtype)


def _rope_half_split(x, theta: float):
    """x [B, T, H, hd]: the two HALVES of a head rotate against each
    other (`rotate_half`), positions 0 .. T - 1."""
    T, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = (x[..., :half].astype(jnp.float32),
              x[..., half:].astype(jnp.float32))
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _dense_attention(q, k, v, window):
    """Plain masked attention, the `attention="dense"` path: grouped
    heads by repeating K and V, `window` None for the whole prefix."""
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * (
        q.shape[-1] ** -0.5)
    below = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    live = below >= 0 if window is None else (below >= 0) & (below < window)
    p = jax.nn.softmax(jnp.where(live[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _attention(cfg: AfmoeConfig, x, layer, kind: str):
    B, T, D = x.shape
    H, KV, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    window = cfg.sliding_window if kind == SLIDING else None
    with jax.named_scope("attn_window" if kind == SLIDING else "attn_full"):
        a = _rms(x, layer["in_norm"], cfg.norm_eps)
        q = (a @ layer["wq"].astype(dt)).reshape(B, T, H, hd)
        k = (a @ layer["wk"].astype(dt)).reshape(B, T, KV, hd)
        v = (a @ layer["wv"].astype(dt)).reshape(B, T, KV, hd)
        gate = a @ layer["w_gate_attn"].astype(dt)
        q = _rms(q, layer["q_norm"], cfg.norm_eps)
        k = _rms(k, layer["k_norm"], cfg.norm_eps)
        if kind == SLIDING:  # full layers use no positions
            q = _rope_half_split(q, cfg.rope_theta)
            k = _rope_half_split(k, cfg.rope_theta)
        if cfg.attention == "flash":
            o = flash_attention(q, k, v, True, 1024, 1024, cfg.interpret,
                                window)
        else:
            o = _dense_attention(q, k, v, window)
        o = o.reshape(B, T, H * hd) * jax.nn.sigmoid(
            gate.astype(jnp.float32)).astype(dt)
        return x + _rms(o @ layer["wo"].astype(dt), layer["post_attn_norm"],
                        cfg.norm_eps)


def _swiglu(m, gate, up, down, dt):
    return (jax.nn.silu(m @ gate.astype(dt)) * (m @ up.astype(dt))
            ) @ down.astype(dt)


def _layer(cfg: AfmoeConfig, x, layer, bias, kind: str):
    """One layer: x [B, T, D] -> (x, counts [num_experts] int32 (zeros
    for a dense layer), held pairs)."""
    B, T, D = x.shape
    x = _attention(cfg, x, layer, kind)
    m = _rms(x, layer["pre_mlp_norm"], cfg.norm_eps)
    if "router" not in layer:
        with jax.named_scope("dense_mlp"):
            f = _swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"],
                        cfg.dtype)
        counts, held = jnp.zeros((cfg.num_experts,), jnp.int32), jnp.int32(0)
    else:
        routed, stats = dropless_moe_train(
            m.reshape(B * T, D), layer, bias, top_k=cfg.top_k,
            scale=cfg.route_scale, route_eps=cfg.route_eps, dtype=cfg.dtype,
            held=cfg.held, kernel=cfg.kernel, interpret=cfg.interpret)
        with jax.named_scope("moe_shared"):
            shared = _swiglu(m, layer["s_gate"], layer["s_up"],
                             layer["s_down"], cfg.dtype)
            f = (shared.astype(jnp.float32) + routed.reshape(B, T, D)
                 ).astype(cfg.dtype)
        counts, held = stats["counts"], stats["held_pairs"]
    return x + _rms(f, layer["post_mlp_norm"], cfg.norm_eps), counts, held


def backbone(cfg: AfmoeConfig, params: Dict, tokens: jax.Array,
             router_bias: Optional[jax.Array] = None):
    """tokens [B, T] -> (final hidden states [B, T, D] after the last
    norm, aux): `aux["counts"]` [expert layers, num_experts] int32, the
    pairs every expert was picked for, and `aux["held_pairs"]` [expert
    layers] int32, those of them this chip computed."""
    if router_bias is None:
        router_bias = init_router_bias(cfg)
    lo, rows = cfg.vocab_slice
    with jax.named_scope("embed"):
        local = tokens - lo
        inside = (local >= 0) & (local < rows)
        x = params["embed"].astype(cfg.dtype)[jnp.clip(local, 0, rows - 1)]
        x = jnp.where(inside[..., None], x, jnp.zeros_like(x))
        if cfg.mup_enabled:
            x = x * jnp.asarray(math.sqrt(cfg.hidden), cfg.dtype)
    counts, held = [], []
    for i, (layer, kind) in enumerate(zip(params["layers"], cfg.layer_types)):
        j = i - cfg.num_dense_layers
        bias = router_bias[j] if j >= 0 else None

        def one(x, layer=layer, bias=bias, kind=kind):
            return _layer(cfg, x, layer, bias, kind)

        x, c, n = checkpoint_block(one)(x)
        if j >= 0:
            counts.append(c)
            held.append(n)
    x = _rms(x, params["norm"], cfg.norm_eps)
    return x, {"counts": jnp.stack(counts), "held_pairs": jnp.stack(held)}


def forward(cfg: AfmoeConfig, params: Dict, tokens: jax.Array,
            router_bias: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, T] -> this chip's slice of the logits [B, T, rows]
    (float32)."""
    x, _ = backbone(cfg, params, tokens, router_bias)
    with jax.named_scope("lm_head"):
        return (x @ params["head"].astype(cfg.dtype)).astype(jnp.float32)


def loss_fn(cfg: AfmoeConfig, params: Dict, tokens: jax.Array,
            router_bias: Optional[jax.Array] = None, mesh=None):
    """tokens [B, T + 1] -> (mean next-token cross entropy over THIS
    slice's logits, aux of `backbone`): float32 reductions over logits
    stored in `cfg.logits_dtype`."""
    _one_chip(mesh)
    x, aux = backbone(cfg, params, tokens[:, :-1], router_bias)
    x = x.reshape(-1, x.shape[-1])
    lo, rows = cfg.vocab_slice
    local = tokens[:, 1:].reshape(-1) - lo
    inside = (local >= 0) & (local < rows)
    with jax.named_scope("lm_head"):
        logits = (x @ params["head"].astype(cfg.dtype)).astype(
            cfg.logits_dtype)
        lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.clip(local, 0, rows - 1)[:, None], axis=-1
        )[:, 0].astype(jnp.float32)
        total = jnp.sum(lse - jnp.where(inside, tgt, 0.0))
    return total / x.shape[0], aux


def update_router_bias(bias, counts, coeff: float):
    """The balancing rule, a layer a row: an expert under the layer's
    mean load has its bias raised by `coeff`, one over it lowered, and
    the step is centred: `b += coeff * (d - mean(d))`, `d = sign(mean(c)
    - c)`, `c` the pairs an expert was picked for in the step."""
    c = counts.astype(jnp.float32)
    d = jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
    return bias + coeff * (d - jnp.mean(d, axis=-1, keepdims=True))


def _one_chip(mesh):
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "models/afmoe.py trains one chip's share of the expert layers "
            "and of the vocabulary; the exchange across chips is not here")


# ----------------------------------------------------------------------
# train step
# ----------------------------------------------------------------------
def init_state(cfg: AfmoeConfig, params: Dict) -> Dict:
    """What the step carries beside the optimizer's state: the
    parameters, and the router's bias that no optimizer touches."""
    return {"params": params, "router_bias": init_router_bias(cfg)}


def make_train_step(cfg: AfmoeConfig, optimizer, mesh=None):
    """step(state, opt_state, tokens) -> (state, opt_state, metrics),
    `state = {"params", "router_bias"}` (`init_state`) and `opt_state =
    optimizer.init(state["params"])`: the bias is in neither the
    gradient nor the optimizer, and moves by its rule after the
    update.  Pure; callers jit it (donating the first two)."""
    _one_chip(mesh)

    def step(state, opt_state, tokens):
        params, bias = state["params"], state["router_bias"]
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, bias), has_aux=True)(params)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                 for g in jax.tree.leaves(grads)))
        with jax.named_scope("router_bias"):
            bias = update_router_bias(bias, aux["counts"],
                                      cfg.load_balance_coeff)
        counts = aux["counts"].astype(jnp.float32)
        metrics = {
            "loss": loss, "grad_norm": gnorm,
            "held_pairs": jnp.sum(aux["held_pairs"]),
            # over the router's experts, the expert layers averaged
            "expert_load_max": jnp.mean(jnp.max(counts, axis=-1)),
            "expert_load_mean": jnp.mean(counts),
            "bias_abs_max": jnp.max(jnp.abs(bias)),
        }
        return {"params": params, "router_bias": bias}, opt_state, metrics

    return step
