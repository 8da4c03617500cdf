"""Mamba-2's state-space recurrence for SERVING, and the short causal
convolution before it: a chunked scan for admission, one step for
decode.

A head `h` of width `P` holds a state `S [P, N]` (`N` the state size):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

`dt_t > 0` a head and step (after its softplus), `A_h < 0` a head, so
the decay `exp(dt_t A_h)` is a DATA-DEPENDENT scalar a head and step;
`B_t`, `C_t` `[N]` are shared by the heads of a group (`G` groups).
The state and every decay are float32; the products take operands of
the caller's `dtype` and accumulate in float32.

`ssd_scan` (Dao and Gu 2024, "state-space duality") walks a row of `T`
tokens in chunks of `CHUNK`: inside a chunk the recurrence is a masked
`[Q, Q]` product, `y_i = sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j)
dt_j x_j` (`cum` the running sum of `dt A` inside the chunk), every
chunk at once; what a chunk leaves behind is one more product; the
states at the chunks' starts follow from a scan over the chunks that
does nothing but decay and add; and a token reads the state its chunk
started from through `exp(cum_i) C_i . S`.  The row may hold SEVERAL
sequences end to end (`seg`, -1 padding: the serve engine's packed
prefill): a token sees `j` of its own sequence only, a chunk's start
state only where its sequence began before the chunk, so every
sequence starts from zero, wherever in a chunk it starts.  Or it
CONTINUES one sequence from a given state (`init`: a chunk of a long
prompt; the state belongs to sequence 0).  The states AFTER the tokens
`ends` names come back (each prompt's last token, the chunk's last real
one): the state a chunk started from, decayed, and the chunk's tokens
up to there.

`ssd_step` is the recurrence itself, one token a row: the state is
read once and written once, elementwise, and a row that is not `live`
keeps its state.

`conv_scan` / `conv_step`: a depthwise causal convolution of `K` taps
with bias over the channels, `y_t = b + sum_d w[K - 1 - d] x_{t - d}`,
then SiLU; its state is the last `K - 1` inputs.  In a packed row a tap
reaches inside its own sequence only; a continued sequence's first
taps read the state.

All of it is plain XLA, on any backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
CHUNK = 128
# the sequence no token belongs to: a row's left edge with no `init`
_NONE = -2


def _heads(t, G: int):
    """`[..., H, P]` -> `[..., G, H / G, P]`: a head beside the others
    of its group."""
    return t.reshape(t.shape[:-2] + (G, t.shape[-2] // G, t.shape[-1]))


def ssd_scan(x, dt, A, B, C, seg, ends, *, init=None, dtype=None,
             chunk: int = CHUNK):
    """x `[T, H, P]`, dt `[T, H]` float32, A `[H]`
    float32, B / C `[T, G, N]`, seg `[T]` int32, ends `[K]` int32 ->
    (y `[T, H, P]` float32, states `[K, H, P, N]` float32: the state
    after token `ends[k]` of that token's sequence).  `init` `[H, P, N]`
    float32: the state sequence 0 had before the row's first token
    (None: every sequence starts from zero)."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    dtype = dtype or x.dtype
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                       for t in (x, dt, B, C))
        seg = jnp.pad(seg, (0, pad), constant_values=-1)
    nc = (T + pad) // Q
    Hg = H // G
    dt = dt.astype(F32)
    seg_c = seg.reshape(nc, Q)
    cum = jnp.cumsum((dt * A.astype(F32)[None]).reshape(nc, Q, H), axis=1)
    xdt = (x.astype(F32) * dt[..., None]).reshape(nc, Q, H, P)
    B_c = B.astype(dtype).reshape(nc, Q, G, N)
    C_c = C.astype(dtype).reshape(nc, Q, G, N)
    # the sequence whose state a chunk starts from
    last_seg = seg_c[:, -1]
    prev_seg = jnp.concatenate([
        jnp.full((1,), _NONE if init is None else 0, seg.dtype),
        last_seg[:-1]])

    # inside a chunk: every (i, j <= i) of one sequence
    row = jnp.arange(Q)
    mask = ((seg_c[:, :, None] == seg_c[:, None, :])
            & (row[:, None] >= row[None, :]))                 # [nc, Qi, Qj]
    cb = jnp.einsum("cign,cjgn->cgij", C_c, B_c,
                    preferred_element_type=F32)               # [nc, G, Q, Q]
    cum_h = jnp.moveaxis(cum, 2, 1)                           # [nc, H, Q]
    decay = jnp.exp(jnp.where(
        mask[:, None], cum_h[:, :, :, None] - cum_h[:, :, None, :], -jnp.inf))
    m = (decay.reshape(nc, G, Hg, Q, Q) * cb[:, :, None]).astype(dtype)
    y = jnp.einsum("cghij,cjghp->cighp", m,
                   _heads(xdt.astype(dtype), G),
                   preferred_element_type=F32)                # [nc,Q,G,Hg,P]

    def left_by(xs, bs, w):
        """What a chunk's `Q` tokens, weighted `w` `[.., Q, H]`, leave
        in a state: `sum_j w_j dt_j x_j B_j^T`."""
        return jnp.einsum("...jghp,...jgn->...ghpn",
                          _heads((xs * w[..., None]).astype(dtype), G), bs,
                          preferred_element_type=F32)

    # what each chunk leaves to the next: its LAST sequence's tokens
    to_end = jnp.where((seg_c == last_seg[:, None])[..., None],
                       jnp.exp(cum[:, -1:, :] - cum), 0.0)    # [nc, Q, H]
    left = left_by(xdt, B_c, to_end).reshape(nc, H, P, N)
    carries = (last_seg == prev_seg)[:, None] * jnp.exp(cum[:, -1, :])

    def over_chunks(S, inputs):
        add, keep = inputs
        return keep[:, None, None] * S + add, S

    S0 = jnp.zeros((H, P, N), F32) if init is None else init.astype(F32)
    _, starts = lax.scan(over_chunks, S0, (left, carries))    # [nc, H, P, N]

    # a token reads the state its chunk started from, where that state
    # is its own sequence's
    reads = jnp.where((seg_c == prev_seg[:, None])[..., None],
                      jnp.exp(cum), 0.0)                      # [nc, Q, H]
    y = y + jnp.einsum(
        "cign,cghpn->cighp", C_c,
        starts.astype(dtype).reshape(nc, G, Hg, P, N),
        preferred_element_type=F32) * _heads(reads[..., None], G)
    y = y.reshape(nc * Q, H, P)[:T]

    # the states after the tokens `ends` names
    ce, ie = ends // Q, ends % Q
    cum_e = cum[ce]                                           # [K, Q, H]
    at = jnp.take_along_axis(cum_e, ie[:, None, None], axis=1)  # [K, 1, H]
    own = ((seg_c[ce] == seg[ends][:, None])
           & (row[None, :] <= ie[:, None]))                   # [K, Q]
    w = jnp.where(own[..., None], jnp.exp(at - cum_e), 0.0)
    kept = jnp.where((seg[ends] == prev_seg[ce])[:, None], jnp.exp(at[:, 0]),
                     0.0)                                     # [K, H]
    states = (kept[:, :, None, None] * starts[ce]
              + left_by(xdt[ce], B_c[ce], w).reshape(-1, H, P, N))
    return y, states


def ssd_step(state, x, dt, A, B, C, live=None):
    """One token a row: state `[R, H, P, N]` float32, x `[R, H, P]`, dt
    `[R, H]` float32, A `[H]`, B / C `[R, G, N]`, live `[R]` bool or
    None -> (y `[R, H, P]` float32, state).  A row that is not live
    keeps its state; its `y` is nobody's."""
    R, H, P, N = state.shape
    G = B.shape[1]
    dt = dt.astype(F32)
    to_heads = lambda t: jnp.repeat(t.astype(F32), H // G, axis=1)  # noqa: E731
    decay = jnp.exp(dt * A.astype(F32)[None])                 # [R, H]
    new = (state * decay[..., None, None]
           + (x.astype(F32) * dt[..., None])[..., None]
           * to_heads(B)[:, :, None, :])
    y = jnp.sum(new * to_heads(C)[:, :, None, :], axis=-1)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return y, new


def conv_scan(x, w, bias, seg, ends, *, prev=None):
    """x `[T, C]`, w `[K, C]` (tap `K - 1` the token itself), bias
    `[C]`, seg `[T]`, ends `[K']` -> (silu(conv) `[T, C]` float32, the
    `K - 1` inputs up to and with token `ends[k]` `[K', K - 1, C]` in
    x's dtype, zeros before its sequence's start).  `prev` `[K - 1, C]`:
    the inputs before the row's first token, of sequence 0."""
    T, C = x.shape
    K = w.shape[0]
    if prev is None:
        prev = jnp.zeros((K - 1, C), x.dtype)
        pseg = jnp.full((K - 1,), _NONE, seg.dtype)
    else:
        pseg = jnp.zeros((K - 1,), seg.dtype)
    ext = jnp.concatenate([prev.astype(x.dtype), x])          # [K - 1 + T, C]
    eseg = jnp.concatenate([pseg, seg])
    wf = w.astype(F32)
    y = jnp.broadcast_to(bias.astype(F32)[None], (T, C))
    for d in range(K):  # the input `d` tokens back
        lo = K - 1 - d
        same = (eseg[lo:lo + T] == seg)[:, None]
        y = y + jnp.where(same, ext[lo:lo + T].astype(F32), 0.0) * wf[lo]
    at = ends[:, None] + 1 + jnp.arange(K - 1)[None, :]       # rows of `ext`
    held = jnp.where((eseg[at] == seg[ends][:, None])[..., None], ext[at],
                     jnp.zeros((), x.dtype))
    return jax.nn.silu(y), held


def conv_step(state, x, w, bias, live=None):
    """state `[R, K - 1, C]`, x `[R, C]` -> (silu(conv) `[R, C]`
    float32, state); a row that is not live keeps its state."""
    ext = jnp.concatenate([state, x[:, None].astype(state.dtype)], axis=1)
    y = bias.astype(F32)[None] + jnp.sum(
        ext.astype(F32) * w.astype(F32)[None], axis=1)
    new = ext[:, 1:]
    if live is not None:
        new = jnp.where(live[:, None, None], new, state)
    return jax.nn.silu(y), new
