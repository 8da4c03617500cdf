"""Plain reference for `JetLM/SDAR-30B-A3B-Chat` (`config.json`,
`model_type: sdar_moe`; generation as the family's public `generate.py`
does it): the Qwen3-MoE decoder layer, every layer an expert layer,
under BLOCK DIFFUSION.  Pre-norm RMSNorm; grouped-query attention from
separate q / k / v projections without bias, an RMSNorm over the 128
values of every q and k head BEFORE the rotary, rotary on the whole
head, scores `q k^T / sqrt(128)` in float32 under the BLOCK-CAUSAL mask
(`i` sees `j` iff `j // B <= i // B`); then `p = softmax(h W_g)` over all
experts, the top k, their weights divided by their sum
(`norm_topk_prob`), each expert a SwiGLU, none shared; an untied head.
A position predicts ITS OWN token: no shift anywhere.  `jax.numpy`,
float32, matmul precision `highest`; no cache, no kernels, no sorting,
no batching; nothing from `ray_tpu`.

`generate` is the family's greedy `low_confidence_dynamic` loop with
EVERY forward over the whole sequence so far.  `replay` is its
teacher-forced form for sequences somebody else generated: for a step
index `s` the sequence is laid out twice, CLEAN and NOISY (every
position decided at step `s` or later a mask), a noisy block attending
the clean blocks before it and itself (`replay_mask`), so ONE forward
covers step `s` of every block.

Departures from the published code, each without effect on a result:
- the experts are walked one at a time over ALL tokens with a per-token
  coefficient (0 where the token did not choose the expert), where the
  published code gathers each expert's tokens: the same sum;
- `generate` keeps no cache, so a block's COMMIT forward (the published
  loop's last forward of a block, whose only effect is the cache) is
  counted but not run: the next block's forward sees the committed
  tokens themselves;
- `replay`'s double layout is the family's TRAINING mask read as an
  evaluator; the published inference code never builds it.

What is ASSUMED of the architecture (the configuration's file lists each
with its reason) is written here as the reference does it: the head
norms (unconditional in the family's block), the half-split rotary at
base `rope_theta` with no scaling, no shift between a position and the
token it predicts, confidence `max softmax(logits)` at temperature 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.deepseek_v3 import (  # noqa: F401  (re-exported)
    F32, _identity, _mm, embed, head, margins, rms_norm, swiglu)


def rope_half(x, theta, pos):
    """x [T, heads, d] rotated in the half-split form at positions `pos`
    [T]."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def block_causal(T: int, B: int):
    """[T, T] bool: row `i` sees column `j` iff `j // B <= i // B`."""
    b = jnp.arange(T) // B
    return b[None, :] <= b[:, None]


def replay_mask(T: int, B: int):
    """([2T, 2T] bool, positions [2T]) of a sequence laid out twice:
    rows `0 .. T` CLEAN (block-causal among themselves: the rows a
    commit forward caches), rows `T .. 2T` NOISY: block `b` of them sees
    the clean blocks before `b` and the noisy block `b` itself."""
    b = jnp.arange(T) // B
    clean = b[None, :] <= b[:, None]
    none = jnp.zeros((T, T), bool)
    noisy = jnp.concatenate([b[None, :] < b[:, None],
                             b[None, :] == b[:, None]], axis=1)
    pos = jnp.arange(T)
    return (jnp.concatenate([jnp.concatenate([clean, none], axis=1), noisy]),
            jnp.concatenate([pos, pos]))


def attention(h, w, *, mask, pos, heads, kv, hd, theta, eps, quant):
    """h [T, D] normed -> the heads' outputs [T, heads * hd] under
    `mask` [T, T], rotated at `pos` [T]."""
    T, G = h.shape[0], heads // kv
    q = _mm(h, w["wq"], quant).reshape(T, heads, hd)
    k = _mm(h, w["wk"], quant).reshape(T, kv, hd)
    v = _mm(h, w["wv"], quant).reshape(T, kv, hd)
    q = rope_half(rms_norm(q, w["q_norm"], eps), theta, pos)
    k = rope_half(rms_norm(k, w["k_norm"], eps), theta, pos)

    def group(args):  # one key/value head's query heads at a time
        qg, kg, vg = args          # [T, G, hd], [T, hd], [T, hd]
        a = jnp.einsum("qgd,kd->gqk", qg, kg, precision="highest")
        a = jnp.where(mask[None], a / math.sqrt(hd), -jnp.inf)
        p = jax.nn.softmax(a, axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, vg, precision="highest")

    o = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(T, kv, G, hd), 1, 0), jnp.moveaxis(k, 1, 0),
        jnp.moveaxis(v, 1, 0)))                           # [kv, T, G, hd]
    return jnp.moveaxis(o, 0, 1).reshape(T, heads * hd)


def route(h, router, top_k, renorm, quant=_identity):
    """(weights [T, k], experts [T, k]): a softmax over ALL experts, the
    top k, with `renorm` divided by their sum."""
    p = jax.nn.softmax(_mm(h, router, quant), axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    return (w / jnp.sum(w, axis=-1, keepdims=True) if renorm else w), idx


def experts(h, w, *, top_k, renorm, quant):
    """Routed experts, one at a time over all tokens.  The expert stacks
    may be of any dtype: each expert is cast to float32 at its turn."""
    weights, idx = route(h, w["router"].astype(F32), top_k, renorm, quant)

    def one(y, inputs):
        e, gate, up, down = inputs
        coef = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)  # [T]
        out = swiglu(h, gate.astype(F32), up.astype(F32), down.astype(F32),
                     quant)
        return y + coef[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(w["e_gate"].shape[0]), w["e_gate"], w["e_up"],
        w["e_down"]))
    return y


def layer(x, w, *, mask, pos, heads, kv, hd, theta, eps, top_k, renorm,
          quant=_identity):
    """x [T, D] float32 -> [T, D]; `w` one layer's weights, any dtype."""
    small = {k: v.astype(F32) for k, v in w.items()
             if not k.startswith("e_")}
    h = rms_norm(x, small["attn_norm"], eps)
    o = attention(h, small, mask=mask, pos=pos, heads=heads, kv=kv, hd=hd,
                  theta=theta, eps=eps, quant=quant)
    x = x + _mm(o, small["wo"], quant)
    h = rms_norm(x, small["mlp_norm"], eps)
    return x + experts(h, {**w, **small}, top_k=top_k, renorm=renorm,
                       quant=quant)


def layer_kwargs(model: dict) -> dict:
    """`layer`'s keywords from the published config's keys."""
    assert not model["mlp_only_layers"] and model["decoder_sparse_step"] == 1
    assert not model["use_sliding_window"] and not model["rope_scaling"]
    return dict(heads=model["num_attention_heads"],
                kv=model["num_key_value_heads"], hd=model["head_dim"],
                theta=float(model["rope_theta"]), eps=model["rms_norm_eps"],
                top_k=model["num_experts_per_tok"],
                renorm=bool(model["norm_topk_prob"]))


def forward(tokens, B: int, ends: dict, layers, kw: dict, mask=None,
            pos=None):
    """tokens [T] -> logits [T, vocab] float32 under the block-causal
    mask of block length `B` (or `mask`, `pos` given).  `layers`: the
    layers' weights, one dict each; `ends`: `tok_emb`, `final_norm`,
    `lm_head`."""
    T = tokens.shape[0]
    mask = block_causal(T, B) if mask is None else mask
    pos = jnp.arange(T) if pos is None else pos
    x = embed(tokens, ends["tok_emb"])
    for w in layers:
        x = layer(x, w, mask=mask, pos=pos, **kw)
    return head(x, ends["final_norm"], ends["lm_head"], kw["eps"])


def confidence(logits):
    """(x0, c): the greedy token and `max softmax(logits)`, float32."""
    return (jnp.argmax(logits, axis=-1),
            jnp.max(jax.nn.softmax(logits.astype(F32), axis=-1), axis=-1))


def transfers(B: int, S: int, s: int) -> int:
    """Positions step `s` of `S` decides at least: `B // S`, one more in
    the first `B mod S` steps."""
    return B // S + (s < B % S)


def choose(c, und, n_s: int, threshold: float):
    """Which undecided positions a step decides (numpy, one block): all
    over the threshold if they are at least `n_s`, else the `n_s` surest
    (ties to the lower position)."""
    sure = und & (c > threshold)
    if sure.sum() >= n_s:
        return sure
    order = sorted(np.flatnonzero(und), key=lambda i: (-c[i], i))
    chosen = np.zeros_like(und)
    chosen[order[:n_s]] = True
    return chosen


def generate(prompt, n: int, B: int, S: int, threshold: float, mask_id: int,
             logits_of):
    """Greedy block diffusion: `prompt` (a list) and `n` new tokens ->
    `(answer [n], decided_at [n], forwards, trace)`.  `logits_of(tokens
    [T]) -> [T, vocab]` is the model's forward under the block-causal
    mask over the WHOLE sequence handed to it.  `trace`: `(block's first
    position, step, logits [B, vocab])` of every denoising forward;
    `forwards` counts those and one commit a block."""
    T = len(prompt)
    seq = list(prompt[:T - T % B])
    blk = np.array(list(prompt[T - T % B:]) + [0] * (B - T % B))
    und = np.arange(B) >= T % B
    dec = np.full(B, -1)
    out, decided, trace, forwards = [], [], [], 0
    end = -(-(T + n) // B) * B
    while len(seq) < end:
        s = 0
        while und.any():
            x = np.where(und, mask_id, blk)
            lg = np.asarray(logits_of(jnp.asarray(seq + list(x))))[-B:]
            trace.append((len(seq), s, lg))
            x0, c = (np.asarray(a) for a in confidence(jnp.asarray(lg)))
            chosen = choose(c, und, transfers(B, S, s), threshold)
            blk = np.where(chosen, x0, blk)
            dec = np.where(chosen, s, dec)
            und = und & ~chosen
            s += 1
            forwards += 1
        forwards += 1          # the commit: nothing to compute here
        seq += [int(t) for t in blk]
        decided += [int(d) for d in dec]
        blk, und, dec = np.zeros(B, int), np.ones(B, bool), np.full(B, -1)
    lo = T - (T - T % B)       # the first block's prompt tokens
    return (seq[T:T + n], decided[lo:lo + n], forwards, trace)


def replay_tokens(prompt, answer, decided_at, B: int, mask_id: int, s: int,
                  T: int):
    """The doubled sequence `[2 T]` of step `s` (numpy int32): clean
    tokens, then the same with every ANSWER position decided at step `s`
    or later a mask; zero-padded to `T` tokens each."""
    full = np.zeros(T, np.int32)
    n = len(prompt) + len(answer)
    full[:n] = list(prompt) + list(answer)
    noisy = full.copy()
    late = np.zeros(T, bool)
    late[len(prompt):n] = np.asarray(decided_at) >= s
    noisy[late] = mask_id
    return np.concatenate([full, noisy])


def replay(prompt, answer, decided_at, B: int, mask_id: int, ends: dict,
           layers, kw: dict):
    """Teacher-forced logits of a generated sequence: `[steps, T,
    vocab]`, row `s` the NOISY half's logits of `replay_tokens(.., s)`:
    at an answer position decided at step `s`, in a block that had a
    step `s`, they are what `generate`'s forward of that (block, step)
    gives.  `T` = the sequence rounded up to whole blocks; a last block
    that the answer's end CUTS is not replayed faithfully (the positions
    past the answer were part of the block when it was generated and
    are zeros here): callers hold answers that end with their block."""
    n = len(prompt) + len(answer)
    T = -(-n // B) * B
    mask, pos = replay_mask(T, B)
    out = []
    for s in range(max(decided_at) + 1):
        toks = replay_tokens(prompt, answer, decided_at, B, mask_id, s, T)
        out.append(forward(jnp.asarray(toks), B, ends, layers, kw, mask=mask,
                           pos=pos)[T:])
    return jnp.stack(out)


def choice_margin(c, decided_at, B: int, S: int, lo: int):
    """How far the served CHOICES sit from the reference's (numpy): `c`
    [steps, n] the reference's confidence at each answer position in
    each step's replay, `decided_at` [n], `lo` the answer's first
    position (its place inside its block).  For every (block, step) in
    which fewer positions were decided than were undecided and exactly
    `transfers` of them (the surest-`n_s` branch): the largest reference
    confidence among the undecided positions NOT chosen less the
    smallest among the chosen, 0 where the reference agrees.  -> a list,
    one value such a (block, step)."""
    decided_at = np.asarray(decided_at)
    out = []
    blocks = (lo + np.arange(len(decided_at))) // B
    for b in np.unique(blocks):
        mine = np.flatnonzero(blocks == b)
        for s in range(int(decided_at[mine].max()) + 1):
            und = decided_at[mine] >= s
            chosen = decided_at[mine] == s
            if (chosen.sum() != transfers(B, S, s)
                    or und.sum() <= chosen.sum()):
                continue
            cs = c[s][mine]
            out.append(max(0.0, float(cs[und & ~chosen].max()
                                      - cs[chosen].min())))
    return out
