"""Llama family, TPU-first.

The fine-tune/serving flagship (BASELINE configs #4/#5: Llama-2 7B LoRA
fine-tune via XLA SPMD; Llama-3-style serving replicas).  Same design
stance as gpt2.py: explicit param pytrees + pure functions, stacked
blocks under `lax.scan` (one compiled block body), logical-axis tree so
TP/FSDP/SP are rule-table swaps, bf16 compute against f32 masters.

Architecture (Llama-2/3 lineage): RMSNorm, rotary position embeddings,
grouped-query attention, SwiGLU MLP, untied LM head.

LoRA is first-class: a separate low-rank adapter pytree; the forward
computes `x@W + (x@A)@B * scale` without materializing merged weights,
and the LoRA train step differentiates the adapter tree only — the
XLA-SPMD equivalent of the reference's torch/peft integration path
(`train/examples/deepspeed/`, `train/lightning/_lightning_utils.py`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import checkpoint_block
from ray_tpu.parallel.ring_attention import plain_attention, select_attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32  # < n_heads => grouped-query attention
    intermediate: int = 11008
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "dense"  # dense | flash | ring | ulysses
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, max_seq_len=8192, dim=4096, n_layers=32,
            n_heads=32, n_kv_heads=8, intermediate=14336, rope_theta=500000.0,
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab_size, max_seq_len=128, dim=64, n_layers=2,
            n_heads=4, n_kv_heads=2, intermediate=128,
        )


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict:
    k = jax.random.split(key, 9)
    L, E = cfg.n_layers, cfg.dim
    hd, H, KV, I = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.intermediate
    std = 0.02
    proj_std = std / math.sqrt(2 * L)

    def n(key, shape, s=std):
        return jax.random.normal(key, shape, dtype=jnp.float32) * s

    return {
        "tok_emb": n(k[0], (cfg.vocab_size, E)),  # head uses its own key
        "blocks": {
            "attn_norm": jnp.ones((L, E)),
            "wq": n(k[1], (L, E, H * hd)),
            "wk": n(k[2], (L, E, KV * hd)),
            "wv": n(k[3], (L, E, KV * hd)),
            "wo": n(k[4], (L, H * hd, E), proj_std),
            "mlp_norm": jnp.ones((L, E)),
            "w_gate": n(k[5], (L, E, I)),
            "w_up": n(k[6], (L, E, I)),
            "w_down": n(k[7], (L, I, E), proj_std),
        },
        "final_norm": jnp.ones((E,)),
        "lm_head": n(k[8], (E, cfg.vocab_size)),
    }


def logical_axes(cfg: LlamaConfig) -> Dict:
    return {
        "tok_emb": ("vocab", "embed"),
        "blocks": {
            "attn_norm": (None, "embed"),
            "wq": (None, "embed", "heads"),
            "wk": (None, "embed", "heads"),
            "wv": (None, "embed", "heads"),
            "wo": (None, "heads", "embed"),
            "mlp_norm": (None, "embed"),
            "w_gate": (None, "embed", "mlp"),
            "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# ----------------------------------------------------------------------
# LoRA adapters
# ----------------------------------------------------------------------
LORA_TARGETS = ("wq", "wk", "wv", "wo")


def init_lora(cfg: LlamaConfig, key: jax.Array, rank: int = 8,
              alpha: float = 16.0,
              targets: Tuple[str, ...] = LORA_TARGETS) -> Dict:
    """Adapter pytree: per target, A [L, in, r] (gaussian) and
    B [L, r, out] (zeros — adapters start as identity)."""
    L = cfg.n_layers
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    dims = {
        "wq": (cfg.dim, H * hd),
        "wk": (cfg.dim, KV * hd),
        "wv": (cfg.dim, KV * hd),
        "wo": (H * hd, cfg.dim),
        "w_gate": (cfg.dim, cfg.intermediate),
        "w_up": (cfg.dim, cfg.intermediate),
        "w_down": (cfg.intermediate, cfg.dim),
    }
    ks = jax.random.split(key, len(targets))
    blocks = {}
    for t, kk in zip(targets, ks):
        din, dout = dims[t]
        blocks[f"{t}_a"] = (
            jax.random.normal(kk, (L, din, rank), jnp.float32) / math.sqrt(din)
        )
        blocks[f"{t}_b"] = jnp.zeros((L, rank, dout), jnp.float32)
    return {"blocks": blocks, "scale": jnp.asarray(alpha / rank, jnp.float32)}


def lora_logical_axes(cfg: LlamaConfig, lora: Dict) -> Dict:
    """A: input dim sharded like the base input ('embed'/'heads'/'mlp');
    r replicated.  B: r replicated; output like the base output."""
    in_ax = {"wq": "embed", "wk": "embed", "wv": "embed", "wo": "heads",
             "w_gate": "embed", "w_up": "embed", "w_down": "mlp"}
    out_ax = {"wq": "heads", "wk": "heads", "wv": "heads", "wo": "embed",
              "w_gate": "mlp", "w_up": "mlp", "w_down": "embed"}
    blocks = {}
    for name in lora["blocks"]:
        t, kind = name.rsplit("_", 1)
        if kind == "a":
            blocks[name] = (None, in_ax[t], None)
        else:
            blocks[name] = (None, None, out_ax[t])
    return {"blocks": blocks, "scale": ()}


def _apply(x, w, dtype, lora_layer=None, name: str = "", scale=None):
    """x @ w with an optional low-rank delta.  `scale` (per-OUTPUT-
    channel, from `quantize_weights_int8`) dequantizes int8 weights on
    the fly: (x @ q) * scale == x @ (q * scale) exactly, because the
    scale is constant along the contraction axis — the matmul runs on
    the int8 payload (upcast to the compute dtype) and HBM only ever
    streams 1 byte/weight."""
    out = x @ w.astype(dtype)
    if scale is not None:
        out = out * scale.astype(dtype)
    if lora_layer is not None and f"{name}_a" in lora_layer:
        a = lora_layer[f"{name}_a"].astype(dtype)
        b = lora_layer[f"{name}_b"].astype(dtype)
        out = out + ((x @ a) @ b) * lora_layer["__scale__"].astype(dtype)
    return out


def _lm_head(x, params, dtype):
    """Final projection to vocab logits in f32, int8-aware (sibling
    `lm_head_scale` leaf => per-vocab-column dequant after the matmul)."""
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"].astype(dtype)
        scale = params.get("lm_head_scale")
        if scale is not None:
            logits = logits * scale.astype(dtype)
        return logits.astype(jnp.float32)


# weights the serve path quantizes; norms and the embedding lookup stay
# in their original dtype (tiny, and tok_emb is a gather, not a matmul)
QUANT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_weights_int8(params: Dict) -> Dict:
    """Symmetric per-output-channel int8 weights for serving.

    Every matmul weight (block projections + lm_head) becomes an int8
    payload with a sibling `<name>_scale` f32 leaf holding one scale
    per output channel (`[L, out]` for blocks, `[vocab]` for the
    head).  The scale axis rides the blocks' layer-scan like any other
    leaf, so `forward` / `decode_step*` pick it up via
    `layer.get("<name>_scale")` with zero structural change; `_apply`
    multiplies it back in after the matmul, which is exact w.r.t.
    scaling because the scale is constant along the contraction.
    Quantization error is the int8 rounding of each weight (<= scale/2
    per element); `tests/test_paged_attention.py` gates greedy argmax
    agreement + bounded logit error on the tiny model."""
    from ray_tpu.ops.paged_attention import quantize_int8

    out = {k: v for k, v in params.items()}
    blocks = dict(out["blocks"])
    for name in QUANT_TARGETS:
        q, s = quantize_int8(blocks[name], axis=1)  # [L,in,out] -> [L,out]
        blocks[name] = q
        blocks[name + "_scale"] = s
    out["blocks"] = blocks
    q, s = quantize_int8(out["lm_head"], axis=0)  # [E,vocab] -> [vocab]
    out["lm_head"] = q
    out["lm_head_scale"] = s
    return out


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
# `jax.named_scope`s (`embed`, `attn`, `mlp`, `norm`, `lm_head`) put the
# block part an op came from into its metadata, which is what a device
# trace prints; they change nothing that is computed.
def _embed(params, tokens, dtype):
    with jax.named_scope("embed"):
        return params["tok_emb"].astype(dtype)[tokens]


def _rms_norm(x, g, eps):
    with jax.named_scope("norm"):
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                      keepdims=True)
        return (x * lax.rsqrt(ms + eps).astype(x.dtype)) * g


def _mlp(cfg, x, layer, lora_layer=None):
    """A block's gated MLP with its pre-norm and residual: the one
    part every forward and decode body shares verbatim."""
    with jax.named_scope("mlp"):
        h = _rms_norm(x, layer["mlp_norm"].astype(cfg.dtype), cfg.norm_eps)
        gate = _apply(h, layer["w_gate"], cfg.dtype, lora_layer, "w_gate",
                      layer.get("w_gate_scale"))
        up = _apply(h, layer["w_up"], cfg.dtype, lora_layer, "w_up",
                    layer.get("w_up_scale"))
        down = _apply(jax.nn.silu(gate) * up, layer["w_down"], cfg.dtype,
                      lora_layer, "w_down", layer.get("w_down_scale"))
        return x + down


class Packed(NamedTuple):
    """Several prompts end to end in ONE row of T tokens (the serve
    engine's packed prefill, `serve/engine_model.py`): what a prefill
    forward needs to treat the row as the prompts it holds.  `last` [K]
    int32: the rows the head runs on (each prompt's last token), so the
    logits come back `[B, K, vocab]`.  `seg` [T] int32: which prompt a
    token belongs to, -1 for padding; a token attends inside its own
    prompt only.  `pos` [T] int32: its position inside that prompt,
    which is where it is rotated.  `seg` and `pos` None: the row is one
    prompt from position 0, right-padded (the plain causal form, which
    every `cfg.attention` has)."""
    last: jax.Array
    seg: Optional[jax.Array] = None
    pos: Optional[jax.Array] = None

    def mask(self):
        """[T, T] bool, `same prompt AND causal`; None without `seg`.
        One prompt's block of it is the causal mask."""
        if self.seg is None:
            return None
        t = jnp.arange(self.seg.shape[0])
        return ((self.seg[:, None] == self.seg[None, :])
                & (t[:, None] >= t[None, :]))


def _rope(x, theta: float, t0=0, pos=None):
    """Rotary embedding over the last dim; x [B, T, H, hd].  t0 may be
    a traced offset (KV-cached decode positions); `pos` [T], when
    given, names each token's position itself (`Packed`)."""
    B, T, H, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if pos is None:
        pos = jnp.asarray(t0, jnp.float32) + jnp.arange(T, dtype=jnp.float32)
    pos = pos.astype(jnp.float32)
    ang = pos[:, None] * freqs[None, :]  # [T, half]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def forward(cfg: LlamaConfig, params: Dict, tokens: jax.Array,
            mesh=None, lora: Optional[Dict] = None,
            return_kv: bool = False, packed: Optional[Packed] = None):
    """tokens [B, T] int32 -> logits [B, T, vocab] (f32).

    With return_kv=True also returns the per-layer post-RoPE K/V
    ([L, B, T, KV, hd] each) — the prefill path of KV-cached decoding
    (reference capability: vLLM-style serving on Ray; here the native
    inference path for serve replicas).

    `packed` (B == 1): the row holds several prompts end to end, see
    `Packed`; the logits are `[B, K, vocab]`, the rows `packed.last` only.
    """
    B, T = tokens.shape
    pos, mask = (None, None) if packed is None else (packed.pos,
                                                     packed.mask())
    x = _embed(params, tokens, cfg.dtype)
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    group = H // KV

    blocks = params["blocks"]
    lora_blocks = None
    if lora is not None:
        lora_blocks = dict(lora["blocks"])

    def body(x, layer):
        if lora is not None:
            layer_lora = {k: v for k, v in layer.items() if k.endswith(("_a", "_b"))}
            layer_lora["__scale__"] = lora["scale"]
            layer = {k: v for k, v in layer.items() if not k.endswith(("_a", "_b"))}
        else:
            layer_lora = None

        def one(xin):
            with jax.named_scope("attn"):
                h = _rms_norm(xin, layer["attn_norm"].astype(cfg.dtype),
                              cfg.norm_eps)
                q = _apply(h, layer["wq"], cfg.dtype, layer_lora, "wq",
                           layer.get("wq_scale"))
                k = _apply(h, layer["wk"], cfg.dtype, layer_lora, "wk",
                           layer.get("wk_scale"))
                v = _apply(h, layer["wv"], cfg.dtype, layer_lora, "wv",
                           layer.get("wv_scale"))
                q = _rope(q.reshape(B, T, H, hd), cfg.rope_theta, pos=pos)
                k_kv = _rope(k.reshape(B, T, KV, hd), cfg.rope_theta,
                             pos=pos)
                v_kv = v.reshape(B, T, KV, hd)
                k, v = k_kv, v_kv
                if group > 1:  # GQA: each kv head serves `group` query heads
                    k = jnp.repeat(k, group, axis=2)
                    v = jnp.repeat(v, group, axis=2)
                if mask is None:
                    o = select_attention(cfg.attention, q, k, v, mesh,
                                         causal=True)
                else:  # the dense form is the one that takes a mask
                    o = plain_attention(q, k, v, mask=mask)
                o = o.reshape(B, T, H * hd)
                x1 = xin + _apply(o, layer["wo"], cfg.dtype, layer_lora, "wo",
                                  layer.get("wo_scale"))
            return _mlp(cfg, x1, layer, layer_lora), k_kv, v_kv

        fn = checkpoint_block(one) if cfg.remat else one
        out, k_kv, v_kv = fn(x)
        return out, ((k_kv, v_kv) if return_kv else None)

    scan_tree = dict(blocks)
    if lora_blocks is not None:
        scan_tree.update(lora_blocks)
    x = x.astype(cfg.dtype)
    x, kv = lax.scan(body, x, scan_tree)
    if packed is not None:
        # the head reads K rows, not T; the batch axis stays, so the
        # product has the shape family it has without `packed`
        x = x[:, packed.last]
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    logits = _lm_head(x, params, cfg.dtype)
    if return_kv:
        return logits, kv
    return logits


def loss_fn(cfg: LlamaConfig, params: Dict, tokens: jax.Array,
            mesh=None, lora: Optional[Dict] = None) -> jax.Array:
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = forward(cfg, params, inputs, mesh, lora)
    # lse - target_logit == -log_softmax[target] without materializing
    # the full [B, T, vocab] log-prob tensor (see gpt2.loss_fn)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


# ----------------------------------------------------------------------
# train steps
# ----------------------------------------------------------------------
def make_train_step(cfg: LlamaConfig, optimizer, mesh=None):
    """Full fine-tune/pretrain step."""

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, mesh)
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        import optax

        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    return step


def make_lora_train_step(cfg: LlamaConfig, optimizer, mesh=None):
    """LoRA step: base params frozen, gradients flow only through the
    adapter pytree (the memory/steps win that makes 7B tuning fit)."""

    def step(base_params, lora_params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda lp: loss_fn(cfg, base_params, tokens, mesh, lora=lp)
        )(lora_params)
        updates, opt_state = optimizer.update(grads, opt_state, lora_params)
        import optax

        lora_params = optax.apply_updates(lora_params, updates)
        return lora_params, opt_state, {"loss": loss}

    return step


def merge_lora(cfg: LlamaConfig, params: Dict, lora: Dict) -> Dict:
    """Bake adapters into the base weights (for serving without the
    adapter matmuls)."""
    out = jax.tree.map(lambda x: x, params)  # shallow-ish copy
    blocks = dict(out["blocks"])
    scale = lora["scale"]
    for name, a in lora["blocks"].items():
        t, kind = name.rsplit("_", 1)
        if kind != "a":
            continue
        b = lora["blocks"][f"{t}_b"]
        blocks[t] = blocks[t] + jnp.einsum("lir,lro->lio", a, b) * scale
    out["blocks"] = blocks
    return out


# ----------------------------------------------------------------------
# KV-cached decoding (the serving inference path)
# ----------------------------------------------------------------------
def forward_with_prefix(cfg: LlamaConfig, params: Dict, tokens: jax.Array,
                        prefix_kv, prefix_len):
    """Suffix forward over an existing prefix KV cache (radix prefix
    reuse: the paged engine's cache-hit prefill path).

    `tokens` [B, S] is the prompt SUFFIX, living at absolute positions
    `prefix_len`..`prefix_len + S - 1`; `prefix_kv` = (k, v), each
    [L, B, Pmax, KV, hd], the gathered (possibly padded) KV of the
    shared prefix — columns at or beyond `prefix_len` are masked out,
    so block-table padding rows cost nothing but FLOPs.  Returns
    (full-suffix logits [B, S, vocab] f32, (k_suf, v_suf) each
    [L, B, S, KV, hd]) — the suffix KV the caller writes into its own
    cache blocks.

    Numerics deliberately mirror `forward`'s dense path
    (`plain_attention`: same einsum forms, same -1e30 mask, softmax in
    the compute dtype) so a prefix-cached prefill produces the same
    greedy tokens as the full-prompt prefill it replaces;
    `tests/test_llm_engine.py` pins the equivalence.
    """
    pk, pv = prefix_kv  # [L, B, Pmax, KV, hd]
    B, S = tokens.shape
    Pmax = pk.shape[2]
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    group = H // KV
    scale = hd ** -0.5

    x = _embed(params, tokens, cfg.dtype)
    # column validity over the concatenated [Pmax + S] axis: live
    # prefix columns, then causal self-attention within the suffix
    cols = jnp.arange(Pmax + S)
    prefix_ok = (cols < prefix_len) & (cols < Pmax)
    suffix_causal = (
        (cols[None, :] >= Pmax)
        & ((cols[None, :] - Pmax) <= jnp.arange(S)[:, None])
    )
    mask = (prefix_ok[None, :] | suffix_causal)[None, None]  # [1,1,S,P+S]

    def body(x, inputs):
        layer, pk_l, pv_l = inputs  # pk_l/pv_l [B, Pmax, KV, hd]
        with jax.named_scope("attn"):
            h = _rms_norm(x, layer["attn_norm"].astype(cfg.dtype),
                          cfg.norm_eps)
            q = _apply(h, layer["wq"], cfg.dtype, scale=layer.get("wq_scale"))
            k = _apply(h, layer["wk"], cfg.dtype, scale=layer.get("wk_scale"))
            v = _apply(h, layer["wv"], cfg.dtype, scale=layer.get("wv_scale"))
            q = _rope(q.reshape(B, S, H, hd), cfg.rope_theta, t0=prefix_len)
            k_suf = _rope(k.reshape(B, S, KV, hd), cfg.rope_theta,
                          t0=prefix_len)
            v_suf = v.reshape(B, S, KV, hd)
            kk = jnp.concatenate([pk_l.astype(cfg.dtype), k_suf], axis=1)
            vv = jnp.concatenate([pv_l.astype(cfg.dtype), v_suf], axis=1)
            if group > 1:  # GQA: each kv head serves `group` query heads
                kk = jnp.repeat(kk, group, axis=2)
                vv = jnp.repeat(vv, group, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
            o = o.reshape(B, S, H * hd)
            x1 = x + _apply(o, layer["wo"], cfg.dtype,
                            scale=layer.get("wo_scale"))
        return _mlp(cfg, x1, layer), (k_suf, v_suf)

    x = x.astype(cfg.dtype)
    x, kv = lax.scan(body, x, (dict(params["blocks"]), pk, pv))
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    logits = _lm_head(x, params, cfg.dtype)
    return logits, kv


def prefill(cfg: LlamaConfig, params: Dict, tokens: jax.Array,
            max_len: int, mesh=None):
    """Process the prompt in one pass and build the KV cache.

    tokens [B, T] -> (last-position logits [B, vocab],
    cache = (k [L, B, max_len, KV, hd], v [...]), length T).
    Reference capability: the prefill phase of LLM serving (the
    vLLM-on-Ray pattern); here a native jittable function.
    """
    B, T = tokens.shape
    logits, (ks, vs) = forward(cfg, params, tokens, mesh, return_kv=True)
    pad = [(0, 0), (0, 0), (0, max_len - T), (0, 0), (0, 0)]
    k_cache = jnp.pad(ks, pad)
    v_cache = jnp.pad(vs, pad)
    return logits[:, -1, :], (k_cache, v_cache)


def _decode_layer(cfg: LlamaConfig, layer: Dict, x, rope, attend):
    """THE decoder layer of a decode step, x [B, 1, d]: norm, q/k/v,
    rotation, attention, `wo`, MLP.  The decode steps differ in one
    thing only, which they hand in: `rope(t)` rotates `[B, 1, heads,
    hd]` at this step's position(s), and `attend(q, k_new, v_new)`
    writes this step's row into the cache and attends, returning `(o,
    cache)` with `o` holding `[B, H, hd]` values in any float type.
    Int8 weights ride the `<name>_scale` leaves here as in `forward`."""
    B = x.shape[0]
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    with jax.named_scope("attn"):
        h = _rms_norm(x, layer["attn_norm"].astype(cfg.dtype), cfg.norm_eps)
        q = _apply(h, layer["wq"], cfg.dtype, scale=layer.get("wq_scale"))
        k = _apply(h, layer["wk"], cfg.dtype, scale=layer.get("wk_scale"))
        v = _apply(h, layer["wv"], cfg.dtype, scale=layer.get("wv_scale"))
        q = rope(q.reshape(B, 1, H, hd))
        k_new = rope(k.reshape(B, 1, KV, hd))
        v_new = v.reshape(B, 1, KV, hd)
        o, cache = attend(q, k_new, v_new)
        o = o.astype(cfg.dtype).reshape(B, 1, H * hd)
        x1 = x + _apply(o, layer["wo"], cfg.dtype,
                        scale=layer.get("wo_scale"))
    return _mlp(cfg, x1, layer), cache


def _decode_dense(cfg: LlamaConfig, params: Dict, token, cache, valid,
                  rope, write):
    """A decode step over a dense cache (k, v), each [L, B, M, KV, hd]:
    `write(cache_l, new)` puts this step's row into one layer's K or V,
    and attention runs over all M slots under `valid` (broadcastable to
    [B, H, 1, M]).  Static shapes throughout (the cache is M-sized and
    masked by position), so a step compiles once."""
    group = cfg.n_heads // cfg.n_kv_heads
    x = _embed(params, token, cfg.dtype)[:, None, :]  # [B,1,d]
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))

    def body(x, inputs):
        layer, kc, vc = inputs  # kc/vc [B, M, KV, hd]

        def attend(q, k_new, v_new):
            k2 = write(kc, k_new.astype(kc.dtype))
            v2 = write(vc, v_new.astype(vc.dtype))
            kk, vv = k2, v2
            if group > 1:
                kk = jnp.repeat(kk, group, axis=2)
                vv = jnp.repeat(vv, group, axis=2)
            # scores over all cache slots, masked beyond pos.  bf16
            # operands with f32 ACCUMULATION (flash-style numerics, the
            # standard decode form; measured equal to explicit .astype(f32)
            # operands on v5e — XLA fuses those casts — but this shape
            # guarantees no cache-sized f32 copy on any backend)
            s = jnp.einsum(
                "bohd,bmhd->bhom", q, kk,
                preferred_element_type=jnp.float32,
            ) * scale  # [B,H,1,M] f32
            s = jnp.where(valid, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(
                "bhom,bmhd->bohd", w.astype(cfg.dtype), vv,
                preferred_element_type=jnp.float32,
            )
            return o, (k2, v2)

        return _decode_layer(cfg, layer, x, rope, attend)

    x = x.astype(cfg.dtype)
    x, cache = lax.scan(body, x, (dict(params["blocks"]), *cache))
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return _lm_head(x[:, 0, :], params, cfg.dtype), cache


def decode_step(cfg: LlamaConfig, params: Dict, token: jax.Array,
                cache, pos):
    """One token of autoregressive decoding at ONE position for the
    whole batch: what `generate`'s fused scan rides, and the tests'
    oracle for `decode_step_rows`.  It exists beside that step for its
    cache write: a `dynamic_update_slice` of one row, where per-row
    positions need a masked select over the whole cache.

    token [B] int32, pos scalar (current sequence length), cache (k, v)
    each [L, B, M, KV, hd] -> (logits [B, vocab], updated cache)."""
    M = cache[0].shape[2]
    # causal-by-position mask over the cache slots
    valid = (jnp.arange(M) <= pos)[None, None, None, :]
    return _decode_dense(
        cfg, params, token, cache, valid,
        lambda t: _rope(t, cfg.rope_theta, t0=pos),
        lambda c, new: lax.dynamic_update_slice(c, new, (0, pos, 0, 0)))


def _rope_at(x, theta: float, pos_b):
    """Rotary embedding for ONE decode step at PER-ROW positions:
    x [B, 1, H, hd], pos_b [B] int32 — the continuous-batching form,
    where every batch slot sits at its own sequence length."""
    B, T, H, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos_b.astype(jnp.float32)[:, None] * freqs[None, :]  # [B, half]
    cos = jnp.cos(ang)[:, None, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def decode_step_rows(cfg: LlamaConfig, params: Dict, token: jax.Array,
                     cache, pos, *, tables=None, live=None,
                     interpret: bool = False):
    """One decode step with PER-ROW positions (continuous batching:
    every slot advances at its own length; reference capability: the
    vLLM-on-Ray serving pattern's step-level scheduling).  token [B]
    int32, pos [B] int32 (current length per row) -> (logits [B, vocab]
    f32, cache).  Rows are independent, so a slot's tokens are what a
    dedicated `generate` would produce.

    `tables` None: `cache` is the dense view (k, v), each [L, B, M, KV,
    hd] (the engine's gather route: CPU, and the tests' reference),
    written by a masked select.  `tables` [B, W] int32 (scratch-block
    padded): `cache` is the paged pool's leaves passed WHOLE, `(k_pool,
    v_pool)` each [L, num_blocks, block_size, KV, hd], or with int8
    pools `(k_pool, v_pool, k_scale, v_scale)`, and comes back as a
    tuple of the same arity; per layer `paged_kv_append` writes the new
    row in place, then `paged_decode_attention` walks each row's blocks
    with an online softmax (`ops/paged_attention.py`; the layer index
    rides the kernels as a scalar-prefetch argument, so the scan never
    slices the pool).  The two routes share their numerics in form
    (write-then-attend, f32 score accumulation, -1e30 mask, f32
    softmax, weights cast to cfg.dtype for the value matmul); the paged
    reduction is blockwise-online, so logits agree to float rounding
    and greedy argmax is preserved (`tests/test_paged_attention.py`).

    `live` [B] bool (the engine's `pos < stop`): a row that is not
    live writes nothing into the cache and, on the paged route, attends
    nothing (`dead_row_positions`); what it computes is nobody's."""
    def rope(t):
        return _rope_at(t, cfg.rope_theta, pos)

    if tables is None:
        M = cache[0].shape[2]
        # per-row causal mask over cache slots
        valid = (jnp.arange(M)[None, :] <= pos[:, None])[:, None, None, :]
        # per-row write mask for the cache update.  A masked SELECT, not
        # a batched scatter: `.at[arange(B), pos].set(...)` lowers to a
        # general scatter that TPU executes catastrophically slowly
        # inside the layer scan (measured ~30x the whole step's
        # bandwidth cost); the select is one dense read+write of the
        # cache the step already reads anyway.
        row = jnp.arange(M)[None, :] == pos[:, None]
        if live is not None:
            row = row & live[:, None]
        row = row[:, :, None, None]
        return _decode_dense(cfg, params, token, cache, valid, rope,
                             lambda c, new: jnp.where(row, new, c))

    from ray_tpu.ops import paged_attention as _pa

    # where a row appends, how far it attends
    w_pos, a_pos = _pa.dead_row_positions(pos, live, tables,
                                          cache[0].shape[2])
    x = _embed(params, token, cfg.dtype)[:, None, :]  # [B,1,d]

    def body(carry, inputs):
        x, pool = carry[0], carry[1:]
        li, layer = inputs

        def attend(q, k_new, v_new):
            if len(pool) == 4:  # int8 payload beside its scales
                kq, ks_new = _pa.quantize_int8(k_new[:, 0])
                vq, vs_new = _pa.quantize_int8(v_new[:, 0])
                kp, vp, ks, vs = _pa.paged_kv_append(
                    *pool[:2], kq, vq, tables, w_pos, li,
                    k_scale=pool[2], v_scale=pool[3], k_new_scale=ks_new,
                    v_new_scale=vs_new, interpret=interpret)
                return _pa.paged_decode_attention(
                    q[:, 0], kp, vp, tables, a_pos, li, k_scale=ks,
                    v_scale=vs, interpret=interpret), (kp, vp, ks, vs)
            kp, vp = _pa.paged_kv_append(
                *pool, k_new[:, 0].astype(pool[0].dtype),
                v_new[:, 0].astype(pool[1].dtype), tables, w_pos, li,
                interpret=interpret)
            return _pa.paged_decode_attention(
                q[:, 0], kp, vp, tables, a_pos, li,
                interpret=interpret), (kp, vp)

        x, pool = _decode_layer(cfg, layer, x, rope, attend)
        return (x, *pool), None

    xs = (jnp.arange(cache[0].shape[0], dtype=jnp.int32),
          dict(params["blocks"]))
    (x, *pool), _ = lax.scan(body, (x.astype(cfg.dtype), *cache), xs)
    x = _rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    return _lm_head(x[:, 0, :], params, cfg.dtype), tuple(pool)


_DECODE_JIT_CACHE: Dict = {}


def _jitted_generate_fn(cfg: LlamaConfig, max_new_tokens: int,
                        greedy: bool, mesh=None):
    """One fused prefill+decode program per (cfg, n_new, greedy): the
    WHOLE generation — prefill and a `lax.scan` over decode steps —
    compiles into a single XLA program, so a request costs ONE
    dispatch instead of `max_new_tokens` host round-trips.  On a real
    deployment the per-dispatch latency is what dominates small-batch
    decode (each python-loop step is a blocking device round-trip);
    scanning the loop on-device removes it entirely.  This is the
    compiler-friendly-control-flow rule applied to serving."""
    key_ = (cfg, max_new_tokens, greedy, id(mesh) if mesh else None)
    fn = _DECODE_JIT_CACHE.get(key_)
    if fn is not None:
        return fn

    def gen(params, prompt, temperature, rng):
        B, T = prompt.shape
        max_len = T + max_new_tokens

        def pick(logits, k):
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                k, logits / temperature, axis=-1
            ).astype(jnp.int32)

        keys = jax.random.split(rng, max_new_tokens)
        logits, cache = prefill(cfg, params, prompt, max_len, mesh)
        tok0 = pick(logits, keys[0])

        def body(carry, k_i):
            tok, cache, pos = carry
            logits, cache = decode_step(cfg, params, tok, cache, pos)
            nt = pick(logits, k_i)
            return (nt, cache, pos + 1), nt

        if max_new_tokens > 1:
            _, toks = lax.scan(
                body, (tok0, cache, jnp.asarray(T, jnp.int32)), keys[1:]
            )  # toks [n-1, B]
            return jnp.concatenate(
                [tok0[:, None], toks.transpose(1, 0)], axis=1
            )
        return tok0[:, None]

    fn = jax.jit(gen)
    # each entry retains compiled executables (host + device memory):
    # bound the cache so a long-lived server with badly-bucketed
    # callers degrades to recompiles, not to unbounded growth
    while len(_DECODE_JIT_CACHE) >= 32:
        _DECODE_JIT_CACHE.pop(next(iter(_DECODE_JIT_CACHE)))
    _DECODE_JIT_CACHE[key_] = fn
    return fn


def generate(cfg: LlamaConfig, params: Dict, prompt: jax.Array,
             max_new_tokens: int, temperature: float = 0.0,
             key: Optional[jax.Array] = None, mesh=None) -> jax.Array:
    """Autoregressive generation: fused prefill + KV-cached decode scan.

    prompt [B, T] int32 -> generated [B, max_new_tokens] int32.
    temperature 0 = greedy; otherwise softmax sampling with `key`.
    One compiled program per (B, T, max_new_tokens, greedy) shape — a
    whole generation is a single device dispatch (see
    `_jitted_generate_fn`); same-shape requests reuse the program.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    fn = _jitted_generate_fn(cfg, max_new_tokens, temperature <= 0.0, mesh)
    return fn(params, prompt,
              jnp.asarray(max(temperature, 1e-6), jnp.float32), key)
