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

All of it is plain XLA, on any backend, but for `ssd_scan(kernel=True)`
(the TPU route; `interpret=True` anywhere): ONE Pallas call a layer, a
grid over (a block of the heads of one group, chunk) with the chunks
walked in order.  A grid step makes the chunk's `C B^T` once, each
head's `[Q, Q]` decay tile and its product with it, and the block's
float32 state is a VMEM scratch that lives from chunk to chunk; neither
they nor the states the chunks start from are written to HBM.  The
chunk an `ends` token lies in writes that token's state.  The sums are
the XLA form's, the products' operands and accumulators too; the XLA
form stays as its reference (`tests/test_ssd.py`).
"""

from __future__ import annotations

import functools

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
             chunk: int = CHUNK, kernel: bool = False,
             interpret: bool = False):
    """x `[T, H, P]`, dt `[T, H]` float32, A `[H]`
    float32, B / C `[T, G, N]`, seg `[T]` int32, ends `[K]` int32 ->
    (y `[T, H, P]` float32, states `[K, H, P, N]` float32: the state
    after token `ends[k]` of that token's sequence).  `init` `[H, P, N]`
    float32: the state sequence 0 had before the row's first token
    (None: every sequence starts from zero).  `kernel`: the whole of it
    as ONE Pallas call (`interpret`: in the interpreter, on any
    backend); neither: plain XLA, the kernel's reference."""
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
    dt = dt.astype(F32)
    seg_c = seg.reshape(nc, Q)
    cum = jnp.cumsum((dt * A.astype(F32)[None]).reshape(nc, Q, H), axis=1)
    B_c = B.astype(dtype).reshape(nc, Q, G, N)
    C_c = C.astype(dtype).reshape(nc, Q, G, N)
    # the sequence whose state a chunk starts from
    last_seg = seg_c[:, -1]
    prev_seg = jnp.concatenate([
        jnp.full((1,), _NONE if init is None else 0, seg.dtype),
        last_seg[:-1]])
    if not (kernel or interpret):
        y, states = _scan_xla(x, dt, cum, B_c, C_c, seg_c, last_seg,
                              prev_seg, ends, init, dtype)
        return y[:T], states

    K = ends.shape[0]
    hb = _head_block(H // G, P, Q, N, K)
    fn = _build_scan(nc, Q, H, P, G, N, K, hb, jnp.dtype(dtype).name,
                     init is not None, bool(interpret))
    # a head's column of `dt` and `cum` beside its block's others; the
    # sums are made where the heads fill the lanes, not in that form
    # (XLA otherwise moves the reshape before them: 0.47 ms a layer)
    cum = lax.optimization_barrier(cum)
    cols = lambda t: t.reshape(nc, Q, H // hb, hb).swapaxes(1, 2)  # noqa: E731
    cum = cols(cum)
    held = () if init is None else (
        init.astype(F32).transpose(2, 0, 1).reshape(N, H * P),)
    y, states = fn(
        last_seg, prev_seg, jnp.clip(ends // Q, 0, nc - 1), ends % Q,
        seg[ends], x.reshape(nc, Q, H * P), cols(dt), cum,
        cum.swapaxes(2, 3), seg_c[:, :, None], seg_c[:, None, :],
        B_c.transpose(0, 2, 3, 1), C_c.reshape(nc, Q, G * N), *held)
    return y.reshape(nc * Q, H, P)[:T], states.reshape(K, H, P, N)


def _scan_xla(x, dt, cum, B_c, C_c, seg_c, last_seg, prev_seg, ends, init,
              dtype):
    """The scan in plain XLA, every chunk at once: x `[nc Q, H, P]`, dt
    `[nc Q, H]` and cum `[nc, Q, H]` float32, B_c / C_c `[nc, Q, G, N]`
    in `dtype`, seg_c `[nc, Q]`, last_seg / prev_seg `[nc]` -> (y,
    states)."""
    nc, Q, H = cum.shape
    seg = seg_c.reshape(-1)
    P = x.shape[-1]
    G, N = B_c.shape[2:]
    Hg = H // G
    xdt = (x.astype(F32) * dt[..., None]).reshape(nc, Q, H, P)
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
    y = y.reshape(nc * Q, H, P)

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


# what a grid step's blocks of the kernel may take of VMEM: the `K` end
# states of a block of heads stay resident across the chunks
_VMEM_LIMIT = 64 * 1024 * 1024


def _head_block(Hg: int, P: int, Q: int, N: int, K: int) -> int:
    """How many heads of one group a grid step of the kernel takes: as
    many as keep a block of `y` (`[Q, heads * P]` float32) within half
    a megabyte and the block's `K` end states within eight."""
    return max(d for d in range(1, Hg + 1) if Hg % d == 0 and (
        d == 1 or (d * P * Q * 4 <= 512 * 1024
                   and K * d * P * N * 4 <= 8 * 1024 * 1024)))


@functools.lru_cache(maxsize=None)
def _build_scan(nc, Q, H, P, G, N, K, hb, dtype, resumes, interpret):
    """The scan as one Pallas call: a grid over (a block of `hb` heads
    of one group, chunk), the chunks walked in order, the block's
    float32 state a VMEM scratch that lives across them.  The state is
    held TRANSPOSED, `[N, hb * P]`: then every product is a plain `[m,
    k] x [k, n]` with the heads side by side on the lanes, as `x` and
    `y` `[T, H * P]` have them, and nothing but `B` (by XLA, 4 MB) and
    the states at the two ends (`init` by XLA, an end state a tile at a
    time here) is transposed.  A head's `[Q, Q]` decay tile, its
    product with `C B^T`, the chunk's `C B^T` itself and the state a
    chunk starts from never leave VMEM; the chunk an end lies in writes
    that end's state."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dt = jnp.dtype(dtype)
    Hg = H // G
    # heads side by side on one tile of lanes
    hp = max(d for d in range(1, hb + 1)
             if hb % d == 0 and (d == 1 or d * P <= 128))
    W = hp * P
    tiles = [slice(t * W, (t + 1) * W) for t in range(hb // hp)]

    def kernel(last_ref, prev_ref, ce_ref, ie_ref, eseg_ref, x_ref, dtc_ref,
               cumc_ref, cumr_ref, segc_ref, segr_ref, bt_ref, c_ref, *rest):
        y_ref, ends_ref, st_scr, xdt_scr = rest[-4:]
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _first():
            if resumes:
                st_scr[...] = rest[0][...]
            else:
                st_scr[...] = jnp.zeros_like(st_scr)

        last, prev = last_ref[c], prev_ref[c]
        segc, segr = segc_ref[...], segr_ref[...]             # [Q, 1], [1, Q]
        i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        # 0 where token i sees token j, -inf elsewhere
        unseen = jnp.where((segc == segr) & (i >= j), 0.0, -jnp.inf)
        Cg, Bt = c_ref[...], bt_ref[...]                      # [Q, N], [N, Q]
        cb = jnp.dot(Cg, Bt, preferred_element_type=F32)      # [Q, Q]
        cumc, cumr, dtc = cumc_ref[...], cumr_ref[...], dtc_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)

        def wide(t, cols):
            """Columns `t * hp ..` of `cols` `[R, hb]`, each over its
            head's `P` lanes: `[R, W]`."""
            out = cols[:, t * hp:t * hp + 1]
            for s in range(1, hp):
                out = jnp.where(lane >= s * P,
                                cols[:, t * hp + s:t * hp + s + 1], out)
            return out

        def left_by(t, at, w):
            """What the chunk's tokens, weighted `w` `[Q, hb]`, leave
            in the tile's states: `[N, W]`."""
            return jnp.dot(Bt, (xdt_scr[:, at] * wide(t, w)).astype(dt),
                           preferred_element_type=F32)

        # a token reads the state its chunk started from, where that
        # state is its own sequence's
        reads = jnp.where(segc == prev, jnp.exp(cumc), 0.0)   # [Q, hb]
        for t, at in enumerate(tiles):
            xdt = x_ref[:, at].astype(F32) * wide(t, dtc)     # [Q, W]
            xdt_scr[:, at] = xdt
            xb = xdt.astype(dt)
            y = None
            for s in range(hp):
                h = t * hp + s
                m = (jnp.exp(cumc[:, h:h + 1] - cumr[h:h + 1, :] + unseen)
                     * cb).astype(dt)
                # the lanes of the tile's other heads: dropped
                yh = jnp.dot(m, xb, preferred_element_type=F32)
                y = yh if y is None else jnp.where(lane >= s * P, yh, y)
            y_ref[:, at] = y + jnp.dot(
                Cg, st_scr[:, at].astype(dt),
                preferred_element_type=F32) * wide(t, reads)

        # the states after the tokens `ends` names in this chunk
        row = lax.broadcasted_iota(jnp.int32, (Q, 1), 0)

        def end_state(k, carry):
            @pl.when(ce_ref[k] == c)
            def _():
                ie, es = ie_ref[k], eseg_ref[k]
                at_e = cumc_ref[pl.ds(ie, 1), :]              # [1, hb]
                w = jnp.where((segc == es) & (row <= ie),
                              jnp.exp(at_e - cumc), 0.0)
                kept = jnp.where(es == prev, jnp.exp(at_e), 0.0)
                for t, at in enumerate(tiles):
                    ends_ref[k, at, :] = (wide(t, kept) * st_scr[:, at]
                                          + left_by(t, at, w)).T
            return carry

        lax.fori_loop(0, K, end_state, 0)

        # what the chunk leaves to the next: its LAST sequence's tokens
        end = cumc[Q - 1:Q, :]                                # [1, hb]
        to_end = jnp.where(segc == last, jnp.exp(end - cumc), 0.0)
        carries = jnp.where(last == prev, jnp.exp(end), 0.0)
        for t, at in enumerate(tiles):
            st_scr[:, at] = (wide(t, carries) * st_scr[:, at]
                             + left_by(t, at, to_end))

    def heads_map(jb, c, *_):
        return (c, 0, jb)

    def cols_map(jb, c, *_):
        return (c, jb, 0, 0)

    def seg_map(jb, c, *_):
        return (c, 0, 0)

    in_specs = [
        pl.BlockSpec((None, Q, hb * P), heads_map),           # x
        pl.BlockSpec((None, None, Q, hb), cols_map),          # dt, a column a head
        pl.BlockSpec((None, None, Q, hb), cols_map),          # cum, likewise
        pl.BlockSpec((None, None, hb, Q), cols_map),          # and a row a head
        pl.BlockSpec((None, Q, 1), seg_map),
        pl.BlockSpec((None, 1, Q), seg_map),
        pl.BlockSpec((None, None, N, Q),
                     lambda jb, c, *_: (c, jb * hb // Hg, 0, 0)),  # B^T
        pl.BlockSpec((None, Q, N),
                     lambda jb, c, *_: (c, 0, jb * hb // Hg)),     # C
    ]
    if resumes:
        in_specs.append(pl.BlockSpec((N, hb * P), lambda jb, c, *_: (0, jb)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(H // hb, nc),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, Q, hb * P), heads_map),
                   # resident across the chunks, written back a block
                   pl.BlockSpec((K, hb * P, N), lambda jb, c, *_: (0, jb, 0))],
        scratch_shapes=[pltpu.VMEM((N, hb * P), F32),
                        pltpu.VMEM((Q, hb * P), F32)],
    )
    return pl.pallas_call(
        kernel,
        name="ssd_scan",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nc, Q, H * P), F32),
                   jax.ShapeDtypeStruct((K, H * P, N), F32)],
        compiler_params=pltpu.CompilerParams(
            # a chunk starts from the state the chunk before it left
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


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
