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
float32.  The kernels take the leaves WHOLE with the layer index as a
scalar-prefetch argument; those that write them update them in place
(`input_output_aliases`), as `ops/paged_attention.py`'s do the pools.

A TOKEN NEEDS THE STATE READ, NOT WRITTEN.  With `G_j` the log-gates
summed over a decode chunk's tokens `0..j` and `S_0`, `z_0` what the
chunk found, the recurrence's `o_j` is

    [e^{G_j} phi(q_j)^T S_0 + sum_{i<=j} e^{G_j - G_i} (q_j . k_i)^2 v_i]
  / [e^{G_j} phi(q_j) . z_0 + sum_{i<=j} e^{G_j - G_i} (q_j . k_i)^2 + eps]

(the identity above, inside one chunk), and the state after the chunk
`e^{G_last} S_0 + sum_i e^{G_last - G_i} phi(k_i) v_i^T`.  So a chunk
of `c` steps is `c - 1` READS and one FLUSH, `c + 1` passes over a
row's 34 MB where a write a token makes `2 c`:

- `retention_read`: one step for every LIVE row that writes no state.
  The grid walks (row, kv head); a step reads one `[65, 128, 128]`
  state block (4.26 MB) and the key sum, reads out the kv head's query
  heads against them and returns the numerators and denominators
  (the DENOMINATORS FIRST: a trace tells the two calls apart by their
  first result); XLA around it scales them by `e^{G_j}`, adds the
  chunk's own tokens' terms (a few scores a row) and holds this
  token's key, value and summed log-gate in a `Pending` beside the
  state.  The state and the key sum are inputs only: no output block,
  no alias.
- `retention_decode`: one step for every LIVE row that WRITES: decay,
  add `phi(k) v^T`, read out, write back.  With `pending=` it is the
  chunk's last step, the FLUSH: the decay is the whole chunk's, and
  the block takes every held token's update and this one's (a held
  token's remaining decay `w` rides on its key: `phi(sqrt(w) k) = w
  phi(k)`), once.  With none it is a single step, the scalar oracle and
  a chunk of one.  All on the VPU in float32 (a 5-row product would
  leave the MXU waiting on its weights), under the block's two copies:
  eight updates a block cost a twelfth more than one (3.18 ms a call
  at 28 live rows for 2.94, PERF.md).
- THE KERNELS' LOOPS OVER THE 65 TILES ARE ROLLED (`_tiles_a_turn`,
  `_READ_SLABS`): with every tile unrolled a call is no faster, and an
  engine takes 6 to 15 s longer to start, every time: a kernel of a
  thousand vector operations is that long to trace and lower, and a
  warm compile cache saves none of it.
- BOTH walk the rows LIVE ROWS FIRST, and the index maps of the steps
  past the last live row name the block the last live step named, so
  Pallas neither copies a dead row's state in nor writes it back: a
  dead row is neither read nor written.
- WHOLE BETWEEN PROGRAMS: a `Pending` lives inside ONE decode chunk
  program (`serve/engine_model.RetentionEngineModel.decode_chunk`),
  empty at its first step and folded in at its last, so prefill,
  harvest and any snapshot see leaves with nothing owed.  A row that
  dies inside a chunk is not flushed and need not be: a dead row stays
  dead to the program's end and its state is never read again (a
  prefill's first chunk zeroes the slot).
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
keysum, every decay and every accumulation float32.  The decode steps,
read and flush, are float32 throughout.  The prefill's products take
their operands in the compute dtype (the score's weights before the value product, the
monomials and the carried state for the read-out and the update), as
the flash kernels do, and accumulate in float32.

`kernel=False` runs the same algorithms in plain XLA (the CPU route
and the kernels' reference); `interpret=True` the kernels in the
Pallas interpreter.  `tests/test_aot_tpu_compile.py` lowers all of them
for a described v5e at the published widths.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

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


class Pending(NamedTuple):
    """A decode chunk's tokens that are not in the state yet, one
    layer's (a model stacks every leaf `[layers, ...]` and scans over
    them): the first `n` of `P` places hold, per row and KV head, a
    token's key and value as the model made them and `G`, the log-gates
    summed from the chunk's first token through that one.  It lives
    inside ONE chunk program: empty (`n` 0) at its first step, folded
    into the state at its last."""
    k: jax.Array    # [B, KV, P, d], the compute dtype
    v: jax.Array    # [B, KV, P, d]
    G: jax.Array    # [B, KV, P] float32
    n: jax.Array    # [] int32, the places held


def pending_shapes(layers: int, slots: int, kv_heads: int, d: int, held: int):
    """A model's `Pending` leaves (`k`, `v`, `G`) for `held` tokens."""
    kv = (layers, slots, kv_heads, held, d)
    return (kv, kv, kv[:-1])


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
def _gate_sums(g, pending: Pending):
    """This token's place in its chunk: `G_j` [B, KV], the log-gates
    summed from the chunk's first token through this one, and per held
    token `e^{G_j - G_i}` [B, KV, P], what is left of it by now (0 for
    a place that holds nothing)."""
    at = jnp.arange(pending.G.shape[-1])
    before = jnp.sum(jnp.where(at == pending.n - 1, pending.G, 0.0), axis=-1)
    Gj = before + g.astype(F32)
    left = jnp.exp(jnp.minimum(Gj[..., None] - pending.G, 0.0))
    return Gj, jnp.where(at < pending.n, left, 0.0)


def _live_first(live):
    """The kernels' scalar arguments: the rows LIVE ROWS FIRST (the
    steps past them are never taken) and how many are live."""
    rows = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    return rows, jnp.sum(live).astype(jnp.int32).reshape(1)


def _grid_maps(KV):
    """Index maps of a (row, kv head) grid over live rows first."""
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

    return state_map, keysum_map, head_map, row_map


def _tiles_a_turn(S: int) -> int:
    """How many of the `S` tiles a kernel's loop takes a turn: the
    largest divisor of `S` up to 16 (13 of 65).  One turn's tiles are
    straight-line code, which is what the compiler packs; every tile
    unrolled is five times the program to trace, lower and compile at
    every start, for the same time a call."""
    return max(k for k in range(1, 17) if S % k == 0)


# value rows a pass of the read-out takes, in slabs of 8: a tile's
# monomials (a load a query head) then serve 4 state loads, 20
# accumulators at 5 query heads.  On the v5e 4 and 8 read alike (1.31
# and 1.32 ms a call at 28 live rows); a slab a pass, six loads a state
# register, reads 1.53 (PERF.md §6, PR 39)
_READ_SLABS = 4


def _tile_coef(s, S):
    """Tile `s`'s coefficient inside a kernel (`s` traced)."""
    return jnp.where((s == 0) | (s == S - 1), 1.0, _C_MID).astype(F32)


def _heads_to_lanes(acc):
    """In a kernel: a slab's partial sums, one `[8, d]` a query head,
    as ONE `[8, d]` block of numerators: lane g is head g's, the sum
    over the monomials' lanes."""
    lane = lax.broadcasted_iota(jnp.int32, acc[0].shape, 1)
    out = jnp.zeros(acc[0].shape, F32)
    for g, a in enumerate(acc):
        out = jnp.where(lane == g, jnp.sum(a, axis=1, keepdims=True), out)
    return out


def _decode_xla(q, ku, vu, dec, state, keysum, live, layer, eps):
    """ku, vu [B, KV, U, d] float32: the `U` rank-1 updates a row's
    state takes after its decay `dec` [B, KV] (one: a single step)."""
    B, H, d = q.shape
    KV = ku.shape[1]
    st, zs = state[layer], keysum[layer]          # [B, KV, S, d, d], [B, KV, S, d]
    pk = phi(ku)                                  # [B, KV, U, S, d]
    new_st = dec[..., None, None, None] * st
    for u in range(ku.shape[2]):
        new_st = new_st + (vu[:, :, u, None, :, None]
                           * pk[:, :, u, :, None, :])
    new_z = dec[..., None, None] * zs + jnp.sum(pk, axis=2)
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
def _build_decode(L, B, KV, G, d, U, interpret):
    """`U` 1: a single step (the key a row of `[KV, d]`, the value
    spread over a `[d, d]` block's lanes).  `U` > 1: the flush, `U`
    updates a block (the keys `[U, d]` a kv head, update u's value in
    lane u of the `[d, d]` block)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = shifts(d)
    Gp = -(-G // 8) * 8          # the kv head's query heads, whole sublanes
    Up = -(-U // 8) * 8
    slabs, K = d // 8, _tiles_a_turn(S)
    state_map, keysum_map, head_map, row_map = _grid_maps(KV)

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
            # the keys: one row, or the flush's `U` rows at once
            ku = k_ref[pl.ds(h, 1), :] if U == 1 else k_ref[...]
            dec1 = dec_ref[pl.ds(h, 1), :]                  # [1, d], one value
            dec8 = jnp.broadcast_to(dec1, (8, d))
            q8 = q_ref[...]                                 # [Gp, d]

            def monomials(t, den):
                for s in (t * K + i for i in range(K)):
                    c = _tile_coef(s, S)
                    pk = ku * pltpu.roll(ku, s, 1) * c
                    pq = q8 * pltpu.roll(q8, s, 1) * c
                    z_new = dec1 * z_ref[h, pl.ds(s, 1), :] + (
                        pk if U == 1 else jnp.sum(pk, axis=0, keepdims=True))
                    zo_ref[h, pl.ds(s, 1), :] = z_new
                    den = den + pq * jnp.broadcast_to(z_new, (Gp, d))
                    for u in range(U):
                        pk_scr[u, s] = jnp.broadcast_to(pk[u:u + 1], (8, d))
                    for g in range(G):
                        pq_scr[g, s] = jnp.broadcast_to(pq[g:g + 1], (8, d))
                return den

            den = lax.fori_loop(0, S // K, monomials, jnp.zeros((Gp, d), F32))
            den_ref[...] = jnp.broadcast_to(
                jnp.sum(den, axis=1, keepdims=True), (Gp, d))

            def slab(j, carry):
                r = pl.multiple_of(j * 8, 8)
                vt = vcol_ref[pl.ds(r, 8), :]               # [8, d]: v[r..] a row
                v8 = [vt] if U == 1 else [
                    jnp.broadcast_to(vt[:, u:u + 1], (8, d)) for u in range(U)]

                def tiles(t, acc):
                    for s in (t * K + i for i in range(K)):
                        new = (dec8 * s_ref[s, pl.ds(r, 8), :]
                               + v8[0] * pk_scr[0, s])
                        for u in range(1, U):
                            new = new + v8[u] * pk_scr[u, s]
                        so_ref[s, pl.ds(r, 8), :] = new
                        acc = tuple(a + new * pq_scr[g, s]
                                    for g, a in enumerate(acc))
                    return acc

                acc = lax.fori_loop(0, S // K, tiles,
                                    (jnp.zeros((8, d), F32),) * G)
                num_ref[pl.ds(r, 8), :] = _heads_to_lanes(acc)
                return carry

            lax.fori_loop(0, slabs, slab, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((None, None, Gp, d), head_map),        # q
            (pl.BlockSpec((None, KV, d), row_map) if U == 1     # k
             else pl.BlockSpec((None, None, Up, d), head_map)),
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
        scratch_shapes=[pltpu.VMEM((U, S, 8, d), F32),
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
                     kernel: bool = False, interpret: bool = False,
                     pending: Pending = None):
    """One decode step of every live row, one layer, the state updated
    in place.  q [B, H, d], k, v [B, KV, d] (any float type), g [B, KV]
    float32 log-gates, `state` / `keysum` the whole leaves
    (`state_shapes`), live [B] bool, layer a scalar int32 (traced OK).
    `pending`: the chunk's earlier tokens, which `retention_read` held
    back; this is then the chunk's LAST step, the FLUSH: the state is
    decayed by the whole chunk's gate and takes every held token's
    update, each under what is left of it, and this token's, read out
    and written back, once.  None: a single step.
    Returns (o [B, H, d] float32, zeros for a dead row; state; keysum);
    a dead row's state and keysum are left as they were."""
    B, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    with jax.named_scope("retention_decode"):
        ku, vu = k.astype(F32)[:, :, None], v.astype(F32)[:, :, None]
        if pending is None:
            dec = jnp.exp(g.astype(F32))
        else:
            # phi(sqrt(w) k) = w phi(k): a held token's remaining decay
            # rides on its key, into the state and the key sum alike
            Gj, left = _gate_sums(g, pending)
            held = (left > 0.0)[..., None]
            ku = jnp.concatenate(
                [jnp.where(held, jnp.sqrt(left)[..., None]
                           * pending.k.astype(F32), 0.0), ku], axis=2)
            vu = jnp.concatenate(
                [jnp.where(held, pending.v.astype(F32), 0.0), vu], axis=2)
            dec = jnp.exp(Gj)
        U = ku.shape[2]
        if not (kernel or interpret):
            o, state, keysum = _decode_xla(q, ku, vu, dec, state, keysum,
                                           live, layer, eps)
            return jnp.where(live[:, None, None], o, 0.0), state, keysum
        fn = _build_decode(state.shape[0], B, KV, G, d, U, bool(interpret))
        Gp = -(-G // 8) * 8
        qp = jnp.pad(q.astype(F32).reshape(B, KV, G, d),
                     ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
        dec = jnp.broadcast_to(dec[..., None], (B, KV, d))
        if U == 1:
            kin = ku[:, :, 0]
            vcol = jnp.broadcast_to(vu[:, :, 0, :, None], (B, KV, d, d))
        else:
            kin = jnp.pad(ku, ((0, 0), (0, 0), (0, -U % 8), (0, 0)))
            vcol = jnp.pad(jnp.swapaxes(vu, -1, -2),
                           ((0, 0), (0, 0), (0, 0), (0, d - U)))
        rows, nlive = _live_first(live)
        num, den, state, keysum = fn(
            jnp.asarray(layer, jnp.int32).reshape(1), rows, nlive,
            qp, kin, dec, vcol, state, keysum)
        num = jnp.swapaxes(num[..., :G], -1, -2)            # [B, KV, G, d]
        o = num / (den[:, :, :G, :1] + eps)
        return (jnp.where(live[:, None, None], o.reshape(B, H, d), 0.0),
                state, keysum)


@functools.lru_cache(maxsize=None)
def _build_read(L, B, KV, G, d, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = shifts(d)
    Gp = -(-G // 8) * 8
    K = _tiles_a_turn(S)
    state_map, keysum_map, head_map, _ = _grid_maps(KV)

    def kernel(layer_ref, rows_ref, nlive_ref, q_ref, s_ref, z_ref,
               den_ref, num_ref, pq_scr):
        i, h = pl.program_id(0), pl.program_id(1)

        @pl.when(i < nlive_ref[0])
        def _step():
            q8 = q_ref[...]                                 # [Gp, d]

            def monomials(t, den):
                for s in (t * K + i for i in range(K)):
                    pq = q8 * pltpu.roll(q8, s, 1) * _tile_coef(s, S)
                    den = den + pq * jnp.broadcast_to(
                        z_ref[h, pl.ds(s, 1), :], (Gp, d))
                    for g in range(G):
                        pq_scr[g, s] = jnp.broadcast_to(pq[g:g + 1], (8, d))
                return den

            den = lax.fori_loop(0, S // K, monomials, jnp.zeros((Gp, d), F32))
            den_ref[...] = jnp.broadcast_to(
                jnp.sum(den, axis=1, keepdims=True), (Gp, d))

            # `_READ_SLABS` slabs of 8 value rows a pass over the tiles:
            # a tile's monomials are loaded once for all of them
            for r in range(0, d, 8 * _READ_SLABS):
                rows = range(r, min(r + 8 * _READ_SLABS, d), 8)

                def tile(s, acc):
                    pq = [pq_scr[g, s] for g in range(G)]
                    sts = [s_ref[s, pl.ds(r8, 8), :] for r8 in rows]
                    return tuple(tuple(a + st * pq[g] for g, a in enumerate(row))
                                 for st, row in zip(sts, acc))

                acc = lax.fori_loop(
                    0, S, tile,
                    ((jnp.zeros((8, d), F32),) * G,) * len(rows))
                for r8, row in zip(rows, acc):
                    num_ref[pl.ds(r8, 8), :] = _heads_to_lanes(row)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((None, None, Gp, d), head_map),        # q
            pl.BlockSpec((None, None, None, S, d, d), state_map),
            pl.BlockSpec((None, None, KV, S, d), keysum_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Gp, d), head_map),        # denominators
            pl.BlockSpec((None, None, d, d), head_map),         # numerators
        ],
        scratch_shapes=[pltpu.VMEM((G, S, 8, d), F32)],
    )
    return pl.pallas_call(
        kernel,
        name="retention_read",
        grid_spec=grid_spec,
        # the denominators FIRST: the flush is told apart in a trace by
        # its first result, the numerators' shape
        out_shape=[jax.ShapeDtypeStruct((B, KV, Gp, d), F32),
                   jax.ShapeDtypeStruct((B, KV, d, d), F32)],
        compiler_params=pltpu.CompilerParams(
            # the steps past the last live row rely on the order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


def retention_read(q, k, v, g, state, keysum, pending: Pending, live, layer,
                   *, eps: float, kernel: bool = False,
                   interpret: bool = False):
    """One decode step of every live row, one layer, that WRITES NO
    STATE: a step of a chunk that is not its last.  The arguments are
    `retention_decode`'s, and `pending` holds the chunk's tokens before
    this one.  The state and the key sum are read once, as the chunk's
    first step found them: with `G_j` this token's summed log-gate and
    `i` over the held tokens and this one,

        o = [e^{G_j} phi(q)^T S_0 + sum_i e^{G_j - G_i} (q . k_i)^2 v_i]
          / [e^{G_j} phi(q) . z_0 + sum_i e^{G_j - G_i} (q . k_i)^2 + eps]

    which is the recurrence's `o` (the module's identity, inside one
    chunk).  Returns (o [B, H, d] float32, zeros for a dead row;
    `pending` with this token held too).  What a dead row holds is
    never read: a row that is dead stays dead to the chunk's end."""
    B, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    with jax.named_scope("retention_read"):
        qg = q.astype(F32).reshape(B, KV, G, d)
        if not (kernel or interpret):
            pq = phi(qg)                                    # [B, KV, G, S, d]
            num = jnp.einsum("bkgsp,bksvp->bkgv", pq, state[layer],
                             precision="highest")
            den = jnp.einsum("bkgsp,bksp->bkg", pq, keysum[layer],
                             precision="highest")
        else:
            fn = _build_read(state.shape[0], B, KV, G, d, bool(interpret))
            Gp = -(-G // 8) * 8
            den, num = fn(
                jnp.asarray(layer, jnp.int32).reshape(1), *_live_first(live),
                jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0))),
                state, keysum)
            num = jnp.swapaxes(num[..., :G], -1, -2)        # [B, KV, G, d]
            den = den[:, :, :G, 0]
        # the chunk's own tokens: the quadratic form, a few scores a
        # row (products and sums, not `dot`s: nothing here is a matmul's
        # size, and float32 stays float32 whatever the default precision)
        Gj, left = _gate_sums(g, pending)
        ki = jnp.concatenate([pending.k, k[:, :, None]], axis=2).astype(F32)
        vi = jnp.concatenate([pending.v, v[:, :, None]], axis=2).astype(F32)
        left = jnp.pad(left, ((0, 0), (0, 0), (0, 1)), constant_values=1.0)
        sc = jnp.sum(qg[:, :, :, None] * ki[:, :, None], axis=-1)  # [B, KV, G, i]
        held = left[:, :, None] > 0.0
        p = jnp.where(held, left[:, :, None] * sc * sc, 0.0)
        e = jnp.exp(Gj)[..., None]                          # [B, KV, 1]
        num = e[..., None] * num + jnp.sum(
            jnp.where(held[..., None], p[..., None] * vi[:, :, None], 0.0),
            axis=3)
        den = e * den + jnp.sum(p, axis=-1)
        o = (num / (den[..., None] + eps)).reshape(B, H, d)

        def hold(buf, new):
            return lax.dynamic_update_index_in_dim(
                buf, new.astype(buf.dtype), pending.n, 2)

        pending = Pending(hold(pending.k, k), hold(pending.v, v),
                          hold(pending.G, Gj), pending.n + 1)
        return jnp.where(live[:, None, None], o, 0.0), pending


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
