"""Power retention (degree 2) as Pallas TPU kernels: a gated linear
attention whose context is a STATE of fixed size a sequence, not rows
that grow (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239).

Per KV head, with `g_t <= 0` the token's log-gate and `phi(u)` the
degree-2 monomials of `u` so that `phi(a) . phi(b) = (a . b)^2`:

    S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T      z_t = e^{g_t} z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps)

which is the same function as the quadratic form `w_ts = exp(G_t - G_s)
(q_t . k_s)^2`, `o_t = sum_s w_ts v_s / (sum_s w_ts + eps)` over `s <= t`
(`tests/test_retention.py` holds the two together).

THE MONOMIALS' LAYOUT.  `phi(u)` is held as `d/2 + 1` TILES of `d`
lanes: tile `s` is `c_s * u * roll(u, s)`, the products of the pairs
`(i, i - s mod d)`, with `c_0 = 1`, `c_s = sqrt 2` for `0 < s < d/2` and
`c_{d/2} = 1`.  Every unordered pair of a shift below `d/2` appears once
(weight 2 = c^2), the diagonal once, and the pairs `(i, i + d/2)` twice,
as the ordered pairs they are: `phi(a) . phi(b) = (a . b)^2` exactly.
At `d` 128 that is 65 x 128 = 8,320 values for the 8,256 distinct
monomials (the 64 half-way pairs lie twice), every tile a whole
register row, and a tile costs one lane rotation and one product
inside a kernel: no gather, no triangular index.

The cache (`serve/engine_model.SlotState`): `state` `[L, slots, KV,
d/2 + 1, d, d]` float32, `state[.., s, v, p]` the coefficient of
monomial `(s, p)` for value dim `v` (tile-major, so a kernel takes a
tile by its leading index), and `keysum` `[L, slots, KV, d/2 + 1, d]`
float32.  Both kernels take the leaves WHOLE with the layer index as a
scalar-prefetch argument and update them in place
(`input_output_aliases`), as `ops/paged_attention.py`'s do the pools.

- `retention_decode`: one step for every LIVE row.  The grid walks
  (row, kv head); the rows are handed over LIVE ROWS FIRST and the
  index maps of the steps past the last live row name the block the
  last live step named, so Pallas neither copies a dead row's state in
  nor writes it back: a dead row is neither read nor written.  A step
  reads one `[65, 128, 128]` state block (4.26 MB), decays it, adds
  `phi(k) v^T`, reads out the kv head's query heads and writes the
  block back: all on the VPU in float32 (a 5-row product would leave
  the MXU waiting on its weights), under the block's two copies.
- `retention_prefill`: a chunked scan over a PACKED row (the engine's
  packed prefill: several prompts end to end, each from a chunk
  boundary).  The grid walks (kv head, chunk); inside a chunk the
  quadratic form (scores squared under the decay mask), across chunks
  the carried state, which lives in the OUTPUT block of the segment's
  slot: a segment's first chunk zeroes it, every chunk reads it out and
  adds to it, and when the next segment names another slot Pallas
  writes it back, so each segment's state at its last real token lands
  in its slot.  Padding (`seg` < 0) has a zero key and a zero log-gate:
  it changes no state and no real token's result.

Precision: q, k, v in the model's compute dtype; the state, the
keysum, every decay and every accumulation float32.  The decode step
is float32 throughout.  The prefill's products take their operands in
the compute dtype (the score's weights before the value product, the
monomials and the carried state for the read-out and the update), as
the flash kernels do, and accumulate in float32.

`kernel=False` runs the same two algorithms in plain XLA (the CPU
route and the kernels' reference); `interpret=True` the kernels in the
Pallas interpreter.  `tests/test_aot_tpu_compile.py` lowers both for a
described v5e at the published widths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_VMEM_LIMIT = 64 * 1024 * 1024


def shifts(d: int) -> int:
    """Tiles of `phi`: the shifts 0 .. d/2."""
    return d // 2 + 1


def monomials(d: int) -> int:
    """Distinct degree-2 monomials of a `d`-vector."""
    return d * (d + 1) // 2


def state_shapes(layers: int, slots: int, kv_heads: int, d: int):
    """The two leaves' shapes: (`state`, `keysum`)."""
    S = shifts(d)
    return ((layers, slots, kv_heads, S, d, d), (layers, slots, kv_heads, S, d))


_C_MID = math.sqrt(2.0)  # a pair that lies once stands for both orders


def _coef(d: int):
    return jnp.asarray([1.0] + [_C_MID] * (d // 2 - 1) + [1.0], F32)


def phi(u):
    """u [..., d] -> the monomials [..., d/2 + 1, d] float32."""
    u = u.astype(F32)
    d = u.shape[-1]
    tiles = jnp.stack([u * jnp.roll(u, s, axis=-1)
                       for s in range(shifts(d))], axis=-2)
    return tiles * _coef(d)[:, None]


# ----------------------------------------------------------------------
# the quadratic form (what the recurrence must equal; tests only)
# ----------------------------------------------------------------------
def retention_quadratic(q, k, v, g, eps: float):
    """One sequence, no state: q [T, H, d], k, v [T, KV, d], g [T, KV]
    log-gates -> o [T, H, d] float32."""
    T, H, d = q.shape
    KV = k.shape[1]
    G = jnp.cumsum(g.astype(F32), axis=0)                       # [T, KV]
    qg = q.astype(F32).reshape(T, KV, H // KV, d)
    s = jnp.einsum("tkgd,skd->kgts", qg, k.astype(F32), precision="highest")
    diff = G.T[:, :, None] - G.T[:, None, :]                    # [KV, T, S]
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jnp.where(causal, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)[:, None] * s * s
    num = jnp.einsum("kgts,skd->tkgd", w, v.astype(F32), precision="highest")
    den = jnp.sum(w, axis=-1).transpose(2, 0, 1)                # [T, KV, G]
    return (num / (den[..., None] + eps)).reshape(T, H, d)


# ----------------------------------------------------------------------
# decode: one step for every live row
# ----------------------------------------------------------------------
def _decode_xla(q, k, v, g, state, keysum, live, layer, eps):
    B, H, d = q.shape
    KV = k.shape[1]
    st, zs = state[layer], keysum[layer]          # [B, KV, S, d, d], [B, KV, S, d]
    dec = jnp.exp(g.astype(F32))
    pk = phi(k)                                   # [B, KV, S, d]
    new_st = (dec[..., None, None, None] * st
              + v.astype(F32)[:, :, None, :, None] * pk[:, :, :, None, :])
    new_z = dec[..., None, None] * zs + pk
    pq = phi(q.reshape(B, KV, H // KV, d))        # [B, KV, G, S, d]
    num = jnp.einsum("bkgsp,bksvp->bkgv", pq, new_st, precision="highest")
    den = jnp.einsum("bkgsp,bksp->bkg", pq, new_z, precision="highest")
    o = (num / (den[..., None] + eps)).reshape(B, H, d)
    keep = live[:, None, None, None]
    state = lax.dynamic_update_index_in_dim(
        state, jnp.where(keep[..., None], new_st, st), layer, 0)
    keysum = lax.dynamic_update_index_in_dim(
        keysum, jnp.where(keep, new_z, zs), layer, 0)
    return o, state, keysum


@functools.lru_cache(maxsize=None)
def _build_decode(L, B, KV, G, d, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = shifts(d)
    Gp = -(-G // 8) * 8          # the kv head's query heads, whole sublanes
    slabs = d // 8

    def where_step(i, h, nlive):
        """The (row index into `rows`, kv head) a grid step names: its
        own while rows are live, the last live step's after that (no
        copy in, no write back), the first row's last head when no row
        is live (that one block is copied through unchanged)."""
        on = i < nlive[0]
        at = jnp.minimum(i, jnp.maximum(nlive[0] - 1, 0))
        return at, jnp.where(on, h, KV - 1)

    def state_map(i, h, layer, rows, nlive):
        at, hh = where_step(i, h, nlive)
        return (layer[0], rows[at], hh, 0, 0, 0)

    def keysum_map(i, h, layer, rows, nlive):
        at, _ = where_step(i, h, nlive)
        return (layer[0], rows[at], 0, 0, 0)

    def head_map(i, h, layer, rows, nlive):
        at, hh = where_step(i, h, nlive)
        return (rows[at], hh, 0, 0)

    def row_map(i, h, layer, rows, nlive):
        at, _ = where_step(i, h, nlive)
        return (rows[at], 0, 0)

    def kernel(layer_ref, rows_ref, nlive_ref, q_ref, k_ref, dec_ref,
               vcol_ref, s_ref, z_ref, num_ref, den_ref, so_ref, zo_ref,
               pk_scr, pq_scr):
        i, h = pl.program_id(0), pl.program_id(1)
        nlive = nlive_ref[0]

        @pl.when((nlive == 0) & (i == 0) & (h == 0))
        def _through():  # nothing live: the one block named is untouched
            so_ref[...] = s_ref[...]
            zo_ref[...] = z_ref[...]

        @pl.when(i < nlive)
        def _step():
            k1 = k_ref[pl.ds(h, 1), :]                      # [1, d]
            dec1 = dec_ref[pl.ds(h, 1), :]                  # [1, d], one value
            dec8 = jnp.broadcast_to(dec1, (8, d))
            q8 = q_ref[...]                                 # [Gp, d]
            den = jnp.zeros((Gp, d), F32)
            for s in range(S):
                c = 1.0 if s in (0, S - 1) else _C_MID
                pk = k1 * pltpu.roll(k1, s, 1) if s else k1 * k1
                pq = q8 * pltpu.roll(q8, s, 1) if s else q8 * q8
                if c != 1.0:
                    pk, pq = pk * c, pq * c
                z_new = dec1 * z_ref[h, pl.ds(s, 1), :] + pk
                zo_ref[h, pl.ds(s, 1), :] = z_new
                den = den + pq * jnp.broadcast_to(z_new, (Gp, d))
                pk_scr[s] = jnp.broadcast_to(pk, (8, d))
                for g in range(G):
                    pq_scr[g, s] = jnp.broadcast_to(pq[g:g + 1], (8, d))
            den_ref[...] = jnp.broadcast_to(
                jnp.sum(den, axis=1, keepdims=True), (Gp, d))

            lane = lax.broadcasted_iota(jnp.int32, (8, d), 1)

            def slab(j, carry):
                r = pl.multiple_of(j * 8, 8)
                v8 = vcol_ref[pl.ds(r, 8), :]               # [8, d]: v[r..] a row
                acc = [jnp.zeros((8, d), F32) for _ in range(G)]
                for s in range(S):
                    new = dec8 * s_ref[s, pl.ds(r, 8), :] + v8 * pk_scr[s]
                    so_ref[s, pl.ds(r, 8), :] = new
                    for g in range(G):
                        acc[g] = acc[g] + new * pq_scr[g, s]
                out = jnp.zeros((8, d), F32)
                for g in range(G):  # column g: query head g's numerator
                    out = jnp.where(
                        lane == g, jnp.sum(acc[g], axis=1, keepdims=True), out)
                num_ref[pl.ds(r, 8), :] = out
                return carry

            lax.fori_loop(0, slabs, slab, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((None, None, Gp, d), head_map),        # q
            pl.BlockSpec((None, KV, d), row_map),               # k
            pl.BlockSpec((None, KV, d), row_map),               # decay
            pl.BlockSpec((None, None, d, d), head_map),         # v, a column
            pl.BlockSpec((None, None, None, S, d, d), state_map),
            pl.BlockSpec((None, None, KV, S, d), keysum_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, d, d), head_map),         # numerators
            pl.BlockSpec((None, None, Gp, d), head_map),        # denominators
            pl.BlockSpec((None, None, None, S, d, d), state_map),
            pl.BlockSpec((None, None, KV, S, d), keysum_map),
        ],
        scratch_shapes=[pltpu.VMEM((S, 8, d), F32),
                        pltpu.VMEM((G, S, 8, d), F32)],
    )
    st_shape, z_shape = state_shapes(L, B, KV, d)
    return pl.pallas_call(
        kernel,
        name="retention_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, d, d), F32),
                   jax.ShapeDtypeStruct((B, KV, Gp, d), F32),
                   jax.ShapeDtypeStruct(st_shape, F32),
                   jax.ShapeDtypeStruct(z_shape, F32)],
        # flattened operand indices, the 3 scalar-prefetch args included
        input_output_aliases={7: 2, 8: 3},
        compiler_params=pltpu.CompilerParams(
            # rows and heads revisit the keysum block and the steps past
            # the last live row rely on the order: sequential
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


def retention_decode(q, k, v, g, state, keysum, live, layer, *, eps: float,
                     kernel: bool = False, interpret: bool = False):
    """One decode step of every live row, one layer, the state updated
    in place.  q [B, H, d], k, v [B, KV, d] (any float type), g [B, KV]
    float32 log-gates, `state` / `keysum` the whole leaves
    (`state_shapes`), live [B] bool, layer a scalar int32 (traced OK).
    Returns (o [B, H, d] float32, zeros for a dead row; state; keysum);
    a dead row's state and keysum are left as they were."""
    B, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    with jax.named_scope("retention_decode"):
        if not (kernel or interpret):
            o, state, keysum = _decode_xla(q, k, v, g, state, keysum, live,
                                           layer, eps)
            return jnp.where(live[:, None, None], o, 0.0), state, keysum
        fn = _build_decode(state.shape[0], B, KV, G, d, bool(interpret))
        Gp = -(-G // 8) * 8
        qp = jnp.pad(q.astype(F32).reshape(B, KV, G, d),
                     ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
        dec = jnp.broadcast_to(jnp.exp(g.astype(F32))[..., None], (B, KV, d))
        vcol = jnp.broadcast_to(v.astype(F32)[..., None], (B, KV, d, d))
        # live rows first; the steps past them are never taken
        rows = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
        nlive = jnp.sum(live).astype(jnp.int32).reshape(1)
        num, den, state, keysum = fn(
            jnp.asarray(layer, jnp.int32).reshape(1), rows, nlive,
            qp, k.astype(F32), dec, vcol, state, keysum)
        num = jnp.swapaxes(num[..., :G], -1, -2)            # [B, KV, G, d]
        o = num / (den[:, :, :G, :1] + eps)
        return (jnp.where(live[:, None, None], o.reshape(B, H, d), 0.0),
                state, keysum)


# ----------------------------------------------------------------------
# prefill: a chunked scan over a packed row
# ----------------------------------------------------------------------
def _chunks(seg, posn, slots, g, k, chunk):
    """What both routes read off a packed row: keys and log-gates with
    the padding's zeroed, the log-gates' running sum inside each chunk,
    and per chunk whether it holds real tokens, whether it starts a
    prompt, and the slot its prompt's state belongs to (a padding
    chunk: the slot of the prompt before it, so that nothing moves)."""
    N = seg.shape[0]
    nc = N // chunk
    real = seg >= 0
    k = jnp.where(real[:, None, None], k, jnp.zeros((), k.dtype))
    g = jnp.where(real[:, None], g.astype(F32), 0.0)
    a = jnp.cumsum(g.reshape(nc, chunk, -1), axis=1).reshape(N, -1)
    head = jnp.arange(nc) * chunk
    valid = real[head]
    first = valid & (posn[head] == 0)
    own = slots[jnp.maximum(seg[head], 0)]
    at = lax.cummax(jnp.where(valid, jnp.arange(nc), -1))
    cslot = jnp.where(at >= 0, own[jnp.maximum(at, 0)], 0)
    return k, a, valid.astype(jnp.int32), first.astype(jnp.int32), \
        cslot.astype(jnp.int32)


def _prefill_xla(q, k, v, a, valid, first, cslot, state, keysum, layer,
                 chunk, eps):
    """The kernel's algorithm in plain XLA: one chunk at a time, the
    carried state read from and written to its slot's place in the
    leaves."""
    N, H, d = q.shape
    KV = k.shape[1]
    G, nc, C = H // KV, N // chunk, chunk
    dt = q.dtype
    t = jnp.arange(C)
    causal = t[:, None] >= t[None, :]
    def step(carry, xs):
        state, keysum = carry
        qc, kc, vc, ac, val, fst, slot = xs     # [C, H, d], [C, KV, d], [C, KV]
        st = jnp.where(fst == 1, 0.0, state[layer, slot])      # [KV, S, d, d]
        zs = jnp.where(fst == 1, 0.0, keysum[layer, slot])     # [KV, S, d]
        qg = qc.reshape(C, KV, G, d)
        sc = jnp.einsum("tkgd,skd->kgts", qg, kc,
                        preferred_element_type=F32)
        at = ac.T                                               # [KV, C]
        dm = jnp.where(causal, jnp.exp(jnp.minimum(
            at[:, :, None] - at[:, None, :], 0.0)), 0.0)        # [KV, t, s]
        p = sc * sc * dm[:, None]
        num = jnp.einsum("kgts,skd->tkgd", p.astype(dt), vc,
                         preferred_element_type=F32)
        den = jnp.sum(p, axis=-1).transpose(2, 0, 1)            # [C, KV, G]
        pq = phi(qg)                                            # [C, KV, G, S, d]
        e_t = jnp.exp(ac)[:, :, None]                           # [C, KV, 1]
        num = num + e_t[..., None] * jnp.einsum(
            "tkgsp,ksvp->tkgv", pq.astype(dt), st.astype(dt),
            preferred_element_type=F32)
        den = den + e_t * jnp.einsum("tkgsp,ksp->tkg", pq, zs,
                                     precision="highest")
        o = (num / (den[..., None] + eps)).reshape(C, H, d)
        pk = phi(kc)                                            # [C, KV, S, d]
        w_end = jnp.exp(ac[-1][None] - ac)                      # [C, KV]
        dec_end = jnp.exp(ac[-1])                               # [KV]
        vw = (vc.astype(F32) * w_end[..., None]).astype(dt)
        new_st = dec_end[:, None, None, None] * st + jnp.einsum(
            "tkv,tksp->ksvp", vw, pk.astype(dt), preferred_element_type=F32)
        new_z = dec_end[:, None, None] * zs + jnp.sum(
            pk * w_end[..., None, None], axis=0)
        state = state.at[layer, slot].set(
            jnp.where(val == 1, new_st, state[layer, slot]))
        keysum = keysum.at[layer, slot].set(
            jnp.where(val == 1, new_z, keysum[layer, slot]))
        return (state, keysum), o

    xs = (q.reshape(nc, C, H, d), k.reshape(nc, C, KV, d),
          v.reshape(nc, C, KV, d), a.reshape(nc, C, KV), valid, first, cslot)
    (state, keysum), o = lax.scan(step, (state, keysum), xs)
    return o.reshape(N, H, d), state, keysum


@functools.lru_cache(maxsize=None)
def _build_prefill(L, slots, N, KV, G, d, C, dtype, eps, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = shifts(d)
    nc = N // C
    dt = jnp.dtype(dtype)

    def coef(s):
        return jnp.where((s == 0) | (s == S - 1), 1.0, _C_MID).astype(F32)

    def state_map(h, c, layer, cslot, valid, first):
        return (layer[0], cslot[c], h, 0, 0, 0)

    def keysum_map(h, c, layer, cslot, valid, first):
        return (layer[0], cslot[c], h, 0, 0)

    def q_map(h, c, *_):
        return (h, 0, c, 0)

    def kv_map(h, c, *_):
        return (h, c, 0)

    def arow_map(h, c, *_):
        return (h, 0, c)

    def end_map(h, c, *_):
        return (h, c, 0, 0)

    def kernel(layer_ref, cslot_ref, valid_ref, first_ref, q_ref, k_ref,
               v_ref, acol_ref, arow_ref, bcol_ref, aend_ref, s_any, z_any,
               o_ref, so_ref, zo_ref, num_scr, den_scr, acc_scr, dacc_scr):
        c = pl.program_id(1)
        valid, first = valid_ref[c], first_ref[c]

        @pl.when((first == 1) | ((c == 0) & (valid == 0)))
        def _fresh():  # a prompt starts: nothing is carried into it
            so_ref[...] = jnp.zeros_like(so_ref)
            zo_ref[...] = jnp.zeros_like(zo_ref)

        @pl.when(valid == 1)
        def _chunk():
            k = k_ref[...]                                  # [C, d]
            kf = k.astype(F32)
            v = v_ref[...]
            ac, ar = acol_ref[...], arow_ref[...]           # [C, 1], [1, C]
            t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
            u = lax.broadcasted_iota(jnp.int32, (C, C), 1)
            dm = jnp.where(t >= u, jnp.exp(jnp.minimum(ac - ar, 0.0)), 0.0)
            # inside the chunk: the quadratic form
            for g in range(G):
                sc = lax.dot_general(q_ref[g], k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=F32)
                p = sc * sc * dm
                num_scr[g] = jnp.dot(p.astype(dt), v,
                                     preferred_element_type=F32)
                den_scr[g] = jnp.sum(p, axis=1, keepdims=True)

            # across chunks: what the prompt's earlier chunks left
            @pl.when(first == 0)
            def _carried():
                acc_scr[...] = jnp.zeros_like(acc_scr)
                dacc_scr[...] = jnp.zeros_like(dacc_scr)

                def tile(s, carry):
                    cs = coef(s)
                    st = so_ref[s].astype(dt)               # [d(v), d(p)]
                    zrow = zo_ref[pl.ds(s, 1), :]           # [1, d]
                    for g in range(G):
                        qf = q_ref[g].astype(F32)
                        pq = qf * pltpu.roll(qf, s, 1) * cs
                        acc_scr[g] += lax.dot_general(
                            pq.astype(dt), st, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)
                        dacc_scr[g] += pq * zrow
                    return carry

                lax.fori_loop(0, S, tile, 0)
                e_t = jnp.exp(ac)
                for g in range(G):
                    num_scr[g] += e_t * acc_scr[g]
                    den_scr[g] += e_t * jnp.sum(dacc_scr[g], axis=1,
                                                keepdims=True)

            for g in range(G):
                o_ref[g] = (num_scr[g] / (den_scr[g] + eps)).astype(o_ref.dtype)

            # the chunk's own keys and values into the carried state
            w_end = jnp.exp(bcol_ref[...])                  # [C, 1], <= 1
            dec_end = jnp.exp(aend_ref[...])                # [1, d], one value
            vwt = (v.astype(F32) * w_end).T.astype(dt)      # [d(v), C]

            def update(s, carry):
                pk = kf * pltpu.roll(kf, s, 1) * coef(s)    # [C, d]
                so_ref[s] = dec_end * so_ref[s] + jnp.dot(
                    vwt, pk.astype(dt), preferred_element_type=F32)
                zo_ref[pl.ds(s, 1), :] = (
                    dec_end * zo_ref[pl.ds(s, 1), :]
                    + jnp.sum(pk * w_end, axis=0, keepdims=True))
                return carry

            lax.fori_loop(0, S, update, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(KV, nc),
        in_specs=[
            pl.BlockSpec((None, G, C, d), q_map),
            pl.BlockSpec((None, C, d), kv_map),
            pl.BlockSpec((None, C, d), kv_map),
            pl.BlockSpec((None, C, 1), kv_map),             # running sum, a column
            pl.BlockSpec((None, 1, C), arow_map),           # and a row
            pl.BlockSpec((None, C, 1), kv_map),             # what is left of it
            pl.BlockSpec((None, None, 1, d), end_map),      # its end, on d lanes
            pl.BlockSpec(memory_space=pl.ANY),              # state: aliased only
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, G, C, d), q_map),
            pl.BlockSpec((None, None, None, S, d, d), state_map),
            pl.BlockSpec((None, None, None, S, d), keysum_map),
        ],
        scratch_shapes=[pltpu.VMEM((G, C, d), F32), pltpu.VMEM((G, C, 1), F32),
                        pltpu.VMEM((G, C, d), F32), pltpu.VMEM((G, C, d), F32)],
    )
    st_shape, z_shape = state_shapes(L, slots, KV, d)
    return pl.pallas_call(
        kernel,
        name="retention_prefill",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((KV, G, N, d), dt),
                   jax.ShapeDtypeStruct(st_shape, F32),
                   jax.ShapeDtypeStruct(z_shape, F32)],
        input_output_aliases={11: 1, 12: 2},
        compiler_params=pltpu.CompilerParams(
            # a chunk reads what the chunk before it left in the block
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


def retention_prefill(q, k, v, g, seg, posn, slots, state, keysum, layer, *,
                      chunk: int, eps: float, kernel: bool = False,
                      interpret: bool = False):
    """Retention over a packed row, one layer: q [N, H, d], k, v [N, KV,
    d] in the compute dtype, g [N, KV] float32 log-gates; seg [N] which
    prompt a token belongs to (-1: padding), posn [N] its position in
    it; slots [K] the slot each prompt's state goes to (an entry no
    token names may hold anything).  Every prompt starts on a multiple of `chunk`
    and its padding follows it.  Returns (o [N, H, d] in q's dtype,
    state, keysum): each prompt's state at its last real token is in
    its slot, the other slots are as they were."""
    N, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    if N % chunk:
        raise ValueError(f"a packed row of {N} tokens is not whole chunks "
                         f"of {chunk}")
    with jax.named_scope("retention_prefill"):
        k, a, valid, first, cslot = _chunks(seg, posn, slots, g, k, chunk)
        if not (kernel or interpret):
            o, state, keysum = _prefill_xla(q, k, v, a, valid, first, cslot,
                                            state, keysum, layer, chunk, eps)
            return o.astype(q.dtype), state, keysum
        fn = _build_prefill(state.shape[0], state.shape[1], N, KV, G, d,
                            int(chunk), jnp.dtype(q.dtype).name, float(eps),
                            bool(interpret))
        at = a.T                                             # [KV, N]
        end = at.reshape(KV, N // chunk, chunk)[:, :, -1]    # a chunk's sum
        left = (end[:, :, None] - at.reshape(KV, -1, chunk)).reshape(KV, N)
        o, state, keysum = fn(
            jnp.asarray(layer, jnp.int32).reshape(1), cslot, valid, first,
            q.reshape(N, KV, G, d).transpose(1, 2, 0, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            at[:, :, None], at[:, None, :], left[:, :, None],
            jnp.broadcast_to(end[:, :, None, None], end.shape + (1, d)),
            state, keysum)
        return o.transpose(2, 0, 1, 3).reshape(N, H, d), state, keysum
