"""Plain reference for GPT-2 (Radford et al. 2019; the
`openai-community/gpt2-medium` `config.json`): learned positions,
pre-norm LayerNorm (eps 1e-5), fused QKV projection with bias, causal
multi-head attention, GELU (tanh form, `gelu_new`) MLP of width 4E,
tied output head, next-token cross entropy.  `jax.numpy`, float32,
matmul precision `highest`; nothing from `ray_tpu`.

The layers are scanned and each is rematerialised in the backward pass
(`jax.checkpoint`): that bounds memory and changes no value.  `quant`
is the control's hook: every matmul operand goes through it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _identity(x):
    return x


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision="highest")


def layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(x, w, n_head, quant):
    """x [T, E] -> [T, E]."""
    T, E = x.shape
    hd = E // n_head
    h = layer_norm(x, w["ln1_g"], w["ln1_b"])
    qkv = _mm(h, w["attn_qkv_w"], quant) + w["attn_qkv_b"]
    q, k, v = [a.reshape(T, n_head, hd) for a in jnp.split(qkv, 3, axis=-1)]
    s = jnp.einsum("qhd,khd->hqk", quant(q), quant(k), precision="highest")
    s = s / jnp.sqrt(jnp.asarray(hd, F32))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", quant(p), quant(v), precision="highest")
    x = x + _mm(o.reshape(T, E), w["attn_out_w"], quant) + w["attn_out_b"]
    h = layer_norm(x, w["ln2_g"], w["ln2_b"])
    h = gelu_new(_mm(h, w["mlp_fc_w"], quant) + w["mlp_fc_b"])
    return x + _mm(h, w["mlp_out_w"], quant) + w["mlp_out_b"]


def logits_one(params, tokens, n_head, quant=_identity):
    """tokens [T] -> logits [T, V]."""
    T = tokens.shape[0]
    x = params["wte"][tokens] + params["wpe"][:T]

    def body(x, w):
        return jax.checkpoint(lambda x, w: block(x, w, n_head, quant))(x, w), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = layer_norm(x, params["lnf_g"], params["lnf_b"])
    return _mm(x, params["wte"].T, quant)


def loss(params, tokens, n_head, quant=_identity):
    """tokens [B, T + 1] -> mean next-token cross entropy."""
    def one(seq):
        lg = logits_one(params, seq[:-1], n_head, quant)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return lse - jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]

    return jnp.mean(jax.lax.map(one, tokens))


def loss_and_grad(params, tokens, n_head, quant=_identity):
    return jax.value_and_grad(lambda p: loss(p, tokens, n_head, quant))(params)
