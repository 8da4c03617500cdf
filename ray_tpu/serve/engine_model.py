"""The seam between the serve engine and a model.

`LlamaEngine` (`serve/llm_engine.py`) is a scheduler over a cache:
admission, shedding, block tables, the radix prefix cache, the tick and
its rings.  What it computes WITH is behind this seam.  An engine model
tells the engine

- its CACHE SPEC (`cache_leaves`): leaves of two kinds, and a model may
  hold EITHER OR BOTH.  PAGED (`kv`, a `PagedKV`): the pool leaves one
  cached token needs, each as the shape after `[layers, num_blocks,
  block_size]` and a dtype, and the bytes one token costs
  (`cache_bytes_per_token`).  PER SLOT (`state`, a `SlotState`): the
  leaves one SEQUENCE holds, each as the shape after `[layers, slots]`,
  and the bytes one slot costs (`cache_bytes_per_slot`).  The paged
  leaves come first.  A leaf says how many layers hold it where that
  is not all of them (`CacheLeaf.layers`): a model whose layers are of
  two kinds has paged K and V in some and a state in the others;
- five program bodies, each keyed by the static shape the engine buckets
  to, all with FLAT signatures so that the engine can jit, name, donate
  and cache them without knowing what the leaves mean:

  - `decode_chunk(W)`: `(params, *cache, tables, tok, pos, stop) ->
    (*cache, tok, pos, toks)`; `toks` is `[1 + chunk (+ aux_rows),
    slots]` int32, row 0 the pre-chunk tokens, and after the chunk's
    rows `aux_rows` rows of the model's own per-tick counters, which
    ride the one device->host read the tokens already cost and come
    back through `tick_fields`.  `stop` [slots] int32 lives on the
    device beside `pos` and `tok`: in each step of the chunk a row is
    LIVE iff `pos < stop`, and only a live row's `pos` advances.  A
    DEAD row (a slot never used: `stop` 0; a budget that ended inside
    the chunk; a finished row whose harvest lags) costs the step
    nothing it can avoid: it attends nothing, it WRITES NOTHING into
    the cache (it may still hold a real table whose blocks the prefix
    cache shares: `ops/paged_attention.dead_row_positions`, the dense
    routes' masked select; a per-slot state stays as it was), the
    expert models route it to no expert, and the token it yields is
    nobody's.  A cache with no paged leaf has no `tables`;
  - `prefill_packed(N)`: `(params, *cache, tokens, seg, posn,
    blk_ids, last, slots, pos0, stop0, pos, tok, stop) -> (*cache, pos,
    tok, stop)`: ADMISSION, a tick's cache-miss prompts in one program
    and one read of the weights.  Up to `K` prompts lie end to end in
    one row of `N` tokens, each starting on a block boundary: `tokens`
    [N]; `seg` [N] which prompt a token belongs to, -1 for padding;
    `posn` [N] its position inside its own prompt; `blk_ids` [N /
    block_size] the pool block each block of the row is cached in
    (padding: the scratch block; a cache with no paged leaf has no
    `blk_ids`).  A token attends inside its own
    prompt only (`same segment AND causal`: one prompt alone is the
    causal mask, so there is no second form), the head runs on the `K`
    rows `last` names (each prompt's last token), the greedy pick is
    taken INSIDE the program, and per prompt `slots`, `pos0` (its
    length) and `stop0` set the admitted rows of the device state; an
    unused entry's slot is out of range and dropped.  The model's
    forward is handed the per-slot leaves and `slots`, and leaves each
    prompt's end state in its slot itself.  Nothing comes
    back to the host.  `K` is 1 where `cfg.attention` is not dense (the
    mask needs the dense form): `seg` and `posn` are then ignored;
  - `prefill(bucket)`: `(params, prompt [1, bucket]) -> (logits
    [bucket, vocab], *kv)`, `kv` the rows to cache, `[L, 1, bucket, ...]`:
    the same model forward with no segments, kept for callers that
    want the logits;
  - `suffix_prefill(s_bucket, p_blocks)`: `(params, *cache, suffix,
    blk_ids, prefix_len) -> (logits, *kv)`: prefill behind a cached
    prefix, read from the pool through `blk_ids`;
  - `kv_write(t_in, nb)`: `(*cache, *kv, blk_ids, slot, pos0, tok0,
    pos, tok, stop0, stop) -> (*cache, pos, tok, stop)`: the prefilled
    rows into their blocks, and the admitted slot's `pos`, `tok` and
    `stop` (`T + n_new - 1`: the engine is greedy with a fixed budget,
    so the host never has to tell the device that a row ended).
    Without `stop0, stop` it returns `(*cache, pos, tok)`.

  A model may have ONE admission family in place of `prefill_packed`,
  `suffix_prefill` and `kv_write` (`packs_suffixes`):
  `suffix_prefill_packed(N)`, a tick's admissions of every kind (a
  prefix hit, a miss, a chunk of a long prompt) in one program, each
  behind its own cached rows through the block table
  (`SparseLatentEngineModel` has the signature).

`PagedKV` states the paged kind's FORMAT once: its leaves, blocks ->
rows of the compute dtype, rows -> blocks (int8 pools with their scale
sidecar are a value of it, not a family of bodies).  `SlotState` states
the second KIND beside it: leaves `[layers, slots, *tail]`, one
state a sequence whatever its length, with no tables, no `blk_ids` and
no gather width.  A model with per-slot leaves has no cached prefix to
prefill behind: `suffix_prefill` and `kv_write` do not exist for it and
the radix prefix cache is refused.
TWO CHUNK PROGRAMS.  `chunk_program` is THE decode chunk of a model
that yields ONE token a live row a step, of every cache: liveness, the
greedy pick, the positions, row 0 and the aux rows, around ONE decode
step the model hands it.  `block_chunk_program` is the chunk of a model
that generates by DIFFUSION OVER BLOCKS: `chunk` FORWARDS of a block of
`B` positions a row, each live row its own state machine (a forward
decides some of the block's positions; the one that decides the last
OUTPUTS the block, `B` tokens out, `pos += B`, and the block's clean
rows are written by the row's next forward, which carries them beside
the next block's own), so a step yields 0 or `B` tokens a row and
`toks` says per slot how many tokens the chunk output, which, and the
step each was decided at.  What the two
share is shared code: the flat signature and the dense view
(`_chunk_args`, `_chunk_cache`), liveness (`pos < stop` at every step;
a dead row writes nothing and is routed to no expert) and the aux rows
behind the tokens (`_chunk_rows`).  What a REQUEST is to the model sits
on `_EngineModel` and is the one-token model's by default: the row's
state beside `pos` and `stop` (`init_tok`: a last token; a
`BlockRows` row), `rows_needed` (the row's `stop`), `first_pos`,
`request_fields` (a request's own generation fields: none, refused),
`pack_extras`, `harvested` (what a chunk's `toks` hold for a slot) and
`device_counts` (the host's mirror of `pos` is a bound, and the live
row-steps come back with the chunk).
`packed_prefill_program` is THE packed prefill: the flat signature,
what the admitted rows start from (`first`: the greedy pick and the
prompt's length, or a block-diffusion row's first block), the paged
rows into their blocks, the admitted rows' state.  `kv_write_program`
is `kv_write`'s flat signature and the admitted slot's state.

Eight implementers: `LlamaEngineModel` (per-head K and V pools),
`LatentMoeEngineModel` (`models/deepseek_v3.py`: ONE latent pool,
absorbed decode attention, dropless experts), `RetentionEngineModel`
(`models/brumby.py`: every layer a power-retention layer, a per-slot
state) and `HybridEngineModel` (`models/lfm2.py`: paged K and V in the
attention layers, a per-slot convolution state in the others, both in
one spec) and `SparseLatentEngineModel` (`models/dots3.py`: latent
attention of two forms with a learned selection; three paged leaves of
different widths and layer counts on one table; one admission family,
its suffixes packed) and `WindowFullEngineModel` (`models/mimo_v2.py`:
BOTH kinds for ATTENTION: paged K and V of different widths for the
full layers, a per-slot RING of window rows for the window layers; a
third admission program, `chunk_prefill`, carries the ring from chunk
to chunk of a long prompt) and `BlockDiffusionEngineModel`
(`models/sdar.py`: folded K and V pools, every layer a softmax-routed
expert layer, `block_chunk_program`; a prefill yields no token and
runs no head) and `RecurrentEngineModel` (`models/nemotron_h.py`: THREE
leaves over disjoint layers, paged K and V in the few attention layers,
a per-slot float32 RECURRENT state and a convolution state in the
Mamba-2 layers, nothing in the expert layers; its `chunk_prefill`
RESUMES the scan from the slot's state, so a long prompt is admitted
chunk by chunk).
`engine_model_for` picks by the config's type, builds the
format from the user's `kv_dtype` and hands the implementer the
resolved route: a user passes a model's config and the model picks its
route.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.exceptions import PrefixCacheUnsupportedError
from ray_tpu.models import (brumby, deepseek_v3, dots3, lfm2, llama,
                            mimo_v2, nemotron_h, sdar)
from ray_tpu.ops import paged_attention as _pa
from ray_tpu.ops import retention as _ret
from ray_tpu.serve.kv_cache import BlockPool, CacheLeaf

KV_DTYPES = ("model", "int8")


class PagedKV:
    """How cached rows lie in a pool's blocks: the one place that knows.

    A cache is a tuple of leaves `[layers, num_blocks, block_size,
    *tail]`, one per entry of `rows` (name -> the tail one token
    caches).  `kv_dtype` "model" stores the rows in the compute dtype
    `dtype`.  "int8" stores a symmetric int8 payload per leaf (half the
    bytes of bf16) and, after the payloads, a float32 scale sidecar per
    leaf with one scale per (layer, row, kv head): the tail without its
    last axis (`ops/paged_attention.quantize_int8`; the paged kernels
    take the leaves as they lie and fuse the dequant).  `used`,
    `layers`: see `CacheLeaf`; one value for every leaf, or a dict by
    the leaf's name where the leaves differ (a model whose layers are
    of two attention forms caches rows of another width in each)."""

    def __init__(self, rows: Dict[str, Tuple[int, ...]], dtype,
                 block_size: int, kv_dtype: str = "model",
                 used=None, layers=None):
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype={kv_dtype!r} not in {KV_DTYPES}")
        self.kv_dtype, self.dtype = kv_dtype, dtype
        self.block_size, self.n_rows = block_size, len(rows)
        self._int8 = kv_dtype == "int8"

        def of(value, name):
            return value.get(name) if isinstance(value, dict) else value

        self.leaves: List[CacheLeaf] = [
            CacheLeaf(name, tail, jnp.int8 if self._int8 else dtype,
                      used=of(used, name), layers=of(layers, name))
            for name, tail in rows.items()]
        if self._int8:
            self.leaves += [
                CacheLeaf(f"{name}_scale", tail[:-1], jnp.float32,
                          sidecar=True, layers=of(layers, name))
                for name, tail in rows.items()]

    def rows(self, cache: Sequence, blk) -> tuple:
        """Blocks -> rows: the blocks `blk` names (`[n]`, one sequence's,
        or `[B, n]`, a batch's tables) as dense rows of the compute
        dtype, one `[L, B, n * block_size, *tail]` per row leaf."""
        lead = blk.shape[:-1] or (1,)

        def take(pool):
            x = jnp.take(pool, blk, axis=1)
            return x.reshape((x.shape[0],) + lead + (-1,) + pool.shape[3:])

        taken = [take(pool) for pool in cache]
        if not self._int8:
            return tuple(taken)
        return tuple(_pa.dequantize_int8(q, s, self.dtype) for q, s in
                     zip(taken[:self.n_rows], taken[self.n_rows:]))

    def write(self, cache: Sequence, blk, rows: Sequence, span=None) -> tuple:
        """Rows -> blocks: `rows` (one array per row leaf, `[L, ..., n *
        block_size, *tail]`, any float type) into the blocks `blk`
        names, `[n]` or `[B, n]`; returns the cache.  `span` = `(lo,
        hi)`, `[B]` positions: the rows are a view that `rows()` made
        and of which only `lo[b] <= row < hi[b]` were written since."""
        def blocks(x, pool):
            return x.reshape((x.shape[0],) + blk.shape + pool.shape[2:])

        if not self._int8:
            # a view's untouched rows are the pool's own bytes: blocks
            # that sequences share get the same values from every sharer
            # and padding rows land in the scratch block, so duplicate
            # indices are benign
            new = [blocks(r.astype(pool.dtype), pool)
                   for pool, r in zip(cache, rows)]
        else:
            quant = [_pa.quantize_int8(r) for r in rows]
            new = [blocks(x, pool) for pool, x in zip(
                cache, [q for q, _ in quant] + [s for _, s in quant])]
            if span is not None:
                # requantize ONLY the rows written since the view was
                # made; untouched rows keep their stored payload+scale
                # bit-exactly, so repeated gather/scatter cycles cannot
                # drift the cache (a full-view requant would re-round
                # every row through the compute dtype each chunk)
                idx = jnp.arange(blk.shape[-1] * self.block_size)[None, :]
                touched = ((idx >= span[0][:, None])
                           & (idx < span[1][:, None])).reshape(
                               (1,) + blk.shape + (self.block_size,))
                new = [jnp.where(
                    jnp.expand_dims(touched, tuple(range(4, x.ndim))),
                    x, jnp.take(pool, blk, axis=1))
                    for pool, x in zip(cache, new)]
        return tuple(pool.at[:, blk].set(x) for pool, x in zip(cache, new))


class SlotState:
    """The second cache KIND: a sequence's context is a STATE of fixed
    size in its slot, not rows that grow.  A cache is a tuple of leaves
    `[layers, slots, *tail]`, one per entry of `leaves` (name -> (the
    tail one sequence holds, dtype)): slot b's state is row b.  There
    are no blocks, no tables and no gather width for these leaves; a
    cache of them alone bounds admission by slots alone
    (`cache_bytes_per_token` 0, `cache_bytes_per_slot` the leaves'
    bytes) and its decode chunk takes no tables
    (`chunk_program(paged=False)`); beside paged leaves
    (`HybridEngineModel`) a request needs a slot AND its blocks.  A
    prefill's end state enters a slot inside the packed prefill
    program, which is handed the slots.  A trie of blocks cannot share
    a state, so the radix prefix cache is refused
    (`PrefixCacheUnsupportedError`).  `layers`: see `CacheLeaf`."""

    def __init__(self, leaves: Dict[str, Tuple[Tuple[int, ...], object]],
                 layers: Optional[int] = None):
        self.leaves: List[CacheLeaf] = [
            CacheLeaf(name, tail, dtype, per_slot=True, layers=layers)
            for name, (tail, dtype) in leaves.items()]


def _chunk_args(flat, paged: bool, gather: Optional[PagedKV]):
    """A chunk program's flat arguments `(*cache, [tables,] tok, pos,
    stop)` as `(pool, cache, tables, tok, pos, stop)`: `pool` the leaves
    as they came, `cache` what the steps run on (`gather`: the dense
    view of the paged leaves, the per-slot leaves behind it)."""
    if paged:
        *pool, tables, tok, pos, stop = flat
    else:
        (*pool, tok, pos, stop), tables = flat, None
    cache = tuple(pool)
    if gather is not None:
        n = len(gather.leaves)
        cache = (*gather.rows(pool[:n], tables), *pool[n:])
    return pool, cache, tables, tok, pos, stop


def _chunk_cache(gather: Optional[PagedKV], pool, tables, cache, span):
    """The cache a chunk program hands back: `gather`'s view written
    into the pool again (`span`: the rows the chunk wrote)."""
    if gather is None:
        return cache
    n = len(gather.leaves)
    return (*gather.write(pool[:n], tables, cache[:gather.n_rows],
                          span=span),
            *cache[gather.n_rows:])


def _chunk_rows(rows: list, counters, slots) -> jax.Array:
    """What a chunk's one device->host read holds: `rows` (each `[n,
    slots]` int32) and behind them the model's aux `counters` (`[n]`,
    or None), each as a row."""
    if counters is not None:
        rows.append(jnp.broadcast_to(
            counters[:, None], counters.shape + slots).astype(jnp.int32))
    return jnp.concatenate(rows, axis=0)


def chunk_program(step, chunk: int, *, gather: Optional[PagedKV] = None,
                  aux=None, paged: bool = True, last_step=None):
    """THE decode chunk of a model that yields ONE token a live row a
    step, `(params, *cache, tables, tok, pos, stop) ->
    (*cache, tok, pos, toks)`: `chunk` greedy steps in one `lax.scan`.
    `paged` False (a cache with no paged leaf): the same without
    `tables`, in the signature and handed to `step` as None.

    `step(params, tok, cache, tables, pos, live) -> (logits, cache,
    stats)` is the model's decode step at per-row positions; `cache` is
    a tuple of arrays in and out, `stats` a tuple of per-step scalars.
    `gather` None: the step runs on the pool in place through the
    tables (the paged kernels).  `gather` the paged leaves' format: the
    step runs on the dense view `[L, slots, W * block_size, ...]` of
    every slot's blocks, and the view goes back into the pool after the
    scan (`pos` before and after it say which rows the chunk wrote);
    per-slot leaves, which lie behind the paged ones, go to the step as
    they are either way.
    `aux(stats)`: the model's `aux_rows` counters, `[aux_rows]`, from
    the steps' stacked stats.
    `last_step` (the signature of `step`): a model whose chunk ENDS in
    a step of another kind (one that writes what the others only held:
    `RetentionEngineModel`) names it, and the chunk is `chunk - 1`
    scanned `step`s and then `last_step` once, as `gather` brackets the
    scan with its view and its write.  None: ONE scan of `chunk`.

    What it shares with `block_chunk_program`: the flat signature and
    the view (`_chunk_args`, `_chunk_cache`), liveness (`pos < stop`
    at every step) and the aux rows (`_chunk_rows`)."""
    def _fn(params, *flat):
        pool, cache, tables, tok, pos, stop = _chunk_args(flat, paged, gather)

        def body(carry, _, step=step):
            tok, cache, pos = carry
            # a row owes a token while it is short of its stop; a dead
            # row stays where it is
            live = pos < stop
            logits, cache, stats = step(params, tok, cache, tables, pos, live)
            nt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nt, cache, jnp.where(live, pos + 1, pos)), (nt, stats)

        # pre-chunk tokens: a freshly admitted slot's FIRST token (from
        # prefill).  Emitting it here as row 0 means admission never
        # needs its own device->host read (one round trip PER REQUEST)
        tok_in, pos_in = tok, pos
        (tok, cache, pos), (toks, stats) = jax.lax.scan(
            body, (tok, cache, pos), None,
            length=chunk if last_step is None else chunk - 1)
        if last_step is not None:
            (tok, cache, pos), ended = body((tok, cache, pos), None, last_step)
            toks, stats = jax.tree.map(
                lambda xs, x: jnp.concatenate([xs, x[None]]),
                (toks, stats), ended)
        cache = _chunk_cache(gather, pool, tables, cache, (pos_in, pos))
        counters = None if aux is None else aux(stats)
        return (*cache, tok, pos,
                _chunk_rows([tok_in[None], toks], counters, tok.shape))

    return _fn


class BlockRows:
    """A block-diffusion row's state beside `pos` and `stop`: ONE int32
    row `[4 B + 4]` a slot, which the engine carries where a one-token
    model's `tok` stands: the block's tokens `blk [B]`, which of them
    are undecided `und [B]` (a flag of the state, never `blk ==
    mask_id`: a prompt that holds the id is only a prompt), the step
    each was decided at `dec [B]` (-1: given by the prompt, or not yet
    decided), the block's step `s`, the request's steps `S` and its
    confidence threshold (a float32's bits), and the PENDING COMMIT: the
    decided tokens `held [B]` of the block before this one, already
    output, and `pend`, whether their clean K and V rows are still owed
    to the cache (they ride in the row's next forward)."""

    def __init__(self, block: int):
        self.block, self.width = block, 4 * block + 4

    def unpack(self, state):
        B = self.block
        return (state[:, :B], state[:, B:2 * B] != 0, state[:, 2 * B:3 * B],
                state[:, 3 * B], state[:, 3 * B + 1],
                jax.lax.bitcast_convert_type(state[:, 3 * B + 2],
                                             jnp.float32),
                state[:, 3 * B + 3:4 * B + 3], state[:, 4 * B + 3] != 0)

    def pack(self, blk, und, dec, s, steps, thr, held=None, pend=None):
        """`held`, `pend` None: nothing pending (a row just admitted)."""
        if held is None:
            held, pend = jnp.zeros_like(blk), jnp.zeros_like(s, bool)
        return jnp.concatenate([
            blk, und.astype(jnp.int32), dec, s[:, None], steps[:, None],
            jax.lax.bitcast_convert_type(thr.astype(jnp.float32),
                                         jnp.int32)[:, None],
            held, pend.astype(jnp.int32)[:, None]], axis=1)


def block_chunk_program(step, unmask, chunk: int, rows: BlockRows,
                        mask_id: int, cap: int, *,
                        gather: Optional[PagedKV] = None, aux=None,
                        commit: bool = True):
    """THE chunk of a model that generates by DIFFUSION OVER BLOCKS,
    `(params, *cache, tables, state, pos, stop) -> (*cache, state, pos,
    toks)`: `chunk` FORWARDS in one `lax.scan`, each live row (`pos <
    stop`, as in `chunk_program`) its own state machine over `state`
    (`BlockRows`), so rows at any step of any block share a forward.

    `step(params, tokens [slots, B], cache, tables, pos, live, commit)
    -> (logits [slots, B, vocab], cache, stats)` is the model's forward
    of one block a row at positions `pos .. pos + B - 1`, which writes
    the block's rows into the cache; its input is `where(und, mask_id,
    blk)`.  A live row takes `unmask(logits, blk, und, dec, s, steps,
    thr) -> (blk, und, dec)`, the denoising choice, and `s += 1`.  The
    forward that decides a block's LAST position OUTPUTS the block: its
    tokens are the row's output, `pos += B`, the next block starts
    undecided at step 0.  The rows that forward wrote had masks in their
    input, and the cache owes the next block the rows a CLEAN input
    writes: A COMMIT IS NEVER A FORWARD OF ITS OWN.  The block's tokens
    stay in the row's state as a pending commit (`held`, `pend`) and
    ride in the row's NEXT forward as its commit half (`commit` =
    `(held, live & pend)`: `sdar.block_step`), beside the next block's
    first denoising forward, which reads them; then `pend` is cleared.
    A pending commit survives a chunk's end in the state.  A request's
    LAST block is never committed: `pos` reaches `stop`, the row is
    dead, and nobody reads those rows (a prefix cache over this model
    would have to commit the last block before adopting it).  A block so
    takes as many forwards as it has denoising steps.  A dead row keeps
    its state.

    `commit` False is the benchmark's control, and no user's option:
    nothing is ever pending and `step` is handed no commit half, so the
    rows a forward with masks in its input wrote stay in the cache.

    `toks` `[2 + 2 cap (+ aux_rows), slots]` int32, `cap` the tokens a
    chunk outputs for a row at most (the model's `advance`: a block a
    forward): row 0 HOW MANY tokens the chunk output for the slot, row 1
    how many forwards it was live in, rows `2 .. 2 + cap` the tokens in
    order, the next `cap` rows the step of its block each was decided at
    (-1: a prompt's token), then `aux(stats)`: `stats` = (live rows that
    decided nothing, rows that denoised, the cached columns the halves
    attended (`pos + B` a live row, `pos` more where a commit rode),
    rows a commit rode in, blocks output, *the step's own) a forward.
    The host learns what a row produced from this read alone: it cannot
    count it, the steps a block takes depend on the request and on the
    logits."""
    B = rows.block

    def _fn(params, *flat):
        pool, cache, tables, state, pos, stop = _chunk_args(flat, True,
                                                            gather)
        slots = pos.shape[0]
        at = jnp.arange(cap, dtype=jnp.int32)[None, :] // B     # [1, cap]

        def body(carry, _):
            state, cache, pos, out, n_tok, n_fwd = carry
            live = pos < stop
            blk, und, dec, s, steps, thr, held, pend = rows.unpack(state)
            riding = live & pend
            logits, cache, stats = step(
                params, jnp.where(und, mask_id, blk), cache, tables, pos,
                live, (held, riding) if commit else None)
            denoise = live & jnp.any(und, axis=-1)
            nblk, nund, ndec = unmask(logits, blk, und, dec, s, steps, thr)
            d = denoise[:, None]
            blk, und, dec = (jnp.where(d, a, b) for a, b in (
                (nblk, blk), (nund, und), (ndec, dec)))
            # out with its last decision, behind what the chunk output
            done = live & ~jnp.any(und, axis=-1)
            here = done[:, None] & (at == (n_tok // B)[:, None])
            out = tuple(jnp.where(here, jnp.tile(x, (1, cap // B)), o)
                        for x, o in zip((blk, dec), out))
            c = done[:, None]
            state = rows.pack(
                jnp.where(c, 0, blk), c | und, jnp.where(c, -1, dec),
                jnp.where(done, 0, jnp.where(denoise, s + 1, s)),
                steps, thr, jnp.where(c, blk, held),
                jnp.where(live, done & commit, pend))
            return ((state, cache, jnp.where(done, pos + B, pos), out,
                     n_tok + B * done, n_fwd + live),
                    (jnp.sum(live & ~denoise), jnp.sum(denoise),
                     jnp.sum(jnp.where(live, pos + B, 0)
                             + jnp.where(riding, pos, 0)),
                     jnp.sum(riding), jnp.sum(done), *stats))

        zero = jnp.zeros((slots,), jnp.int32)
        pos_in, owed = pos, rows.unpack(state)[-1]
        (state, cache, pos, out, n_tok, n_fwd), stats = jax.lax.scan(
            body, (state, cache, pos,
                   (jnp.zeros((slots, cap), jnp.int32),) * 2, zero, zero),
            None, length=chunk)
        # from a pending commit's rows to the open block's
        cache = _chunk_cache(
            gather, pool, tables, cache,
            (jnp.where(owed, jnp.maximum(pos_in - B, 0), pos_in), pos + B))
        counters = None if aux is None else aux(stats)
        return (*cache, state, pos, _chunk_rows(
            [n_tok[None], n_fwd[None], out[0].T, out[1].T], counters,
            pos.shape))

    return _fn


def kv_write_program(kv: PagedKV, fit):
    """`kv_write`, `(*cache, *kv, blk_ids, slot, pos0, tok0, pos, tok
    [, stop0, stop]) -> (*cache, pos, tok [, stop])`: the prefilled
    rows, which `fit(*kv)` cuts or pads to exactly `blk_ids`' blocks,
    into the pool, and the admitted slot's rows of the device state.  A
    caller that only wants blocks written passes no stop."""
    n = len(kv.leaves)

    def _fn(*flat):
        cache, rows = flat[:n], flat[n:n + kv.n_rows]
        blk_ids, slot, pos0, tok0, pos, tok, *stop = flat[n + kv.n_rows:]
        cache = kv.write(cache, blk_ids, fit(*rows))
        state = (pos.at[slot].set(pos0), tok.at[slot].set(tok0))
        if stop:
            state += (stop[1].at[slot].set(stop[0]),)
        return (*cache, *state)

    return _fn


def _admitted(pos, tok, stop, slots, pos0, tok0, stop0) -> tuple:
    """A packed prefill's admitted rows of the device state; an unused
    entry's slot is past the last one: dropped."""
    return (pos.at[slots].set(pos0, mode="drop"),
            tok.at[slots].set(tok0, mode="drop"),
            stop.at[slots].set(stop0, mode="drop"))


def _greedy_first(logits, _tokens, _last, pos0):
    """What a one-token model's admission sets: the prompt's length and
    the token picked from its last row's logits."""
    return pos0, jnp.argmax(logits[0], axis=-1).astype(jnp.int32)


def packed_prefill_program(kv: Optional[PagedKV], n_state: int, forward,
                           fit=lambda *rows: rows, segmented: bool = True,
                           first=_greedy_first, extras: int = 0):
    """THE packed prefill, `(params, *cache, tokens, seg, posn, blk_ids,
    last, slots, pos0, stop0, pos, tok, stop) -> (*cache, pos, tok,
    stop)`, of a cache of `kv`'s paged leaves (None: none, and no
    `blk_ids` in the signature) and `n_state` per-slot leaves behind
    them.  `forward(params, state, tokens [1, N], packed, slots) ->
    (logits [1, K, vocab], rows, state)` is the model's prefill forward
    under a `llama.Packed`: `rows` what the paged leaves cache, which
    `fit(*rows)` turns into `[L, 1, N, ...]` as the pool holds them
    (where they are not that already) and which then reshape straight
    into `blk_ids`' blocks; `state` the per-slot leaves, each prompt's
    end state left in its slot by the forward itself.

    `first(logits, tokens, last, pos0, *extra) -> (pos0, tok0)`: what
    the admitted rows' `pos` and `tok` are set to (the greedy pick and
    the prompt's length; a model whose prefill yields no token makes
    its rows' first state here), `extra` the `extras` per-prompt arrays
    `[K]` the host puts behind `stop0` (`_EngineModel.pack_extras`)."""
    n_paged = len(kv.leaves) if kv is not None else 0

    def _fn(params, *flat):
        paged = flat[:n_paged]
        state = flat[n_paged:n_paged + n_state]
        *host, pos, tok, stop = flat[n_paged + n_state:]
        extra = host[len(host) - extras:]
        (tokens, seg, posn, *blk_ids, last, slots, pos0,
         stop0) = host[:len(host) - extras]
        packed = (llama.Packed(last, seg, posn) if segmented
                  else llama.Packed(last))
        logits, rows, state = forward(params, tuple(state), tokens[None],
                                      packed, slots)
        pos0, tok0 = first(logits, tokens, last, pos0, *extra)
        if kv is not None:
            paged = kv.write(paged, blk_ids[0], fit(*rows))
        return (*paged, *state,
                *_admitted(pos, tok, stop, slots, pos0, tok0, stop0))

    return _fn


class _EngineModel:
    """What the engine reads off any implementer besides its five
    bodies: `cache_leaves` (the paged leaves of `kv`, then the per-slot
    leaves of `state`; either may be None), `n_layers`, `kv_dtype`,
    `segmented` (a packed prefill may hold several prompts), `aux_rows`
    and `tick_fields` (the model's own per-tick counters; none here),
    `state_write_deferred` (a per-slot state is written at a chunk's
    last step only, not at every step), `packs_suffixes` (ONE admission
    family, `suffix_prefill_packed`: a tick's admissions, hits, misses
    and chunks of long prompts alike, share a program, each behind its
    own rows through the block table; it then has no `prefill_packed`,
    `suffix_prefill` or `kv_write`), `pack_align` (the rows each of
    them is aligned to in that program) and `state_carries_chunks` (a
    per-slot leaf that a prompt admitted CHUNK BY CHUNK can carry from
    one chunk's program to the next, and the model then has
    `chunk_prefill`: a ring of window rows does, and so does a
    RECURRENT state whose scan resumes from the slot's state
    (`RecurrentEngineModel`); Brumby's retention state and LFM2's
    convolution state as wired today are what a prefill leaves ONCE,
    and do not)."""

    aux_rows = 0
    state_write_deferred = False
    packs_suffixes = False
    state_carries_chunks = False
    # what a row yields is COUNTED ON THE DEVICE (a block-diffusion
    # model): the host's mirror of `pos` is then an upper bound, and the
    # tick's live row-steps come back with the chunk (`tick_fields`)
    device_counts = False
    # the `tick_fields` the engine also sums from its start (`stats()`)
    summed: Tuple[str, ...] = ()
    # a prompt's cached prefix can be prefilled behind (`suffix_prefill`
    # and `kv_write`, or `packs_suffixes`); per-slot leaves never can
    shares_prefix = True

    def __init__(self, cfg, kv: Optional[PagedKV], *, chunk: int,
                 paged: bool, interpret: bool,
                 state: Optional[SlotState] = None):
        self.cfg, self.kv, self.state, self.chunk = cfg, kv, state, chunk
        self._paged, self._interpret = paged, interpret
        # a chunk's rows of `toks` before the aux rows; the positions a
        # live row moves in a chunk at most (`advance`) and may touch
        # past its `pos` (`reach`)
        self.token_rows = 1 + chunk
        self.advance = self.reach = chunk
        self.n_layers = cfg.n_layers
        self.cache_leaves = ((kv.leaves if kv is not None else [])
                             + (state.leaves if state is not None else []))
        self.kv_dtype = kv.kv_dtype if kv is not None else "model"
        # the segment mask needs the dense attention form
        self.segmented = getattr(cfg, "attention", "dense") == "dense"

    def tick_fields(self, aux) -> Dict[str, object]:
        return {}

    # -- what a REQUEST is to the model (one token a step, here) --------
    def init_tok(self, slots: int) -> jax.Array:
        """The slots' device state beside `pos` and `stop`: a row's last
        token."""
        return jnp.zeros((slots,), jnp.int32)

    def rows_needed(self, T: int, n_new: int) -> int:
        """The cache rows a prompt of `T` tokens and `n_new` new ones
        need, which is the row's `stop`: the highest index a WANTED
        token's step touches is `T + n_new - 2`."""
        return T + n_new - 1

    def first_pos(self, T: int) -> int:
        """A row's `pos` at admission."""
        return T

    def request_fields(self, denoising_steps=None,
                       confidence_threshold=None) -> Dict[str, object]:
        """A request's own generation fields, validated (`submit`).  A
        model that yields one greedy token a step has none."""
        if denoising_steps is not None or confidence_threshold is not None:
            raise ValueError(
                f"{type(self.cfg).__name__} generates one token a step: it "
                "takes no denoising_steps / confidence_threshold")
        return {}

    def pack_extras(self, K: int, reqs: Sequence[Dict]) -> tuple:
        """A packed prefill's per-prompt arrays `[K]` behind `stop0`,
        from the packed requests' `fields`; none here."""
        return ()

    def harvested(self, toks_host, slot: int, first: bool) -> tuple:
        """`(tokens, more)` a harvested chunk holds for `slot`: here a
        token a step, from row 0 (the prefill's token) in the request's
        FIRST chunk and from row 1 after; the engine cuts them to what
        the request still wants.  `more`: None, or what else the device
        counted for the row."""
        return toks_host[0 if first else 1:self.token_rows, slot], None

    def context_fields(self, contexts: Sequence[int]) -> Dict[str, object]:
        """The model's own tick fields of the contexts (tokens a row
        attends over, its own included) of the rows live at a chunk's
        first step; none here."""
        return {}

    def _no_prefix(self, *_):
        raise PrefixCacheUnsupportedError(
            "a per-slot state has no cached prefix to prefill behind")


class LlamaEngineModel(_EngineModel):
    """Per-head K / V pools `[L, num_blocks, block_size, KV, hd]` in the
    format `kv` (`PagedKV`).  `paged`: the decode step reads and writes
    the pool in place through the Pallas kernels (`interpret`: in the
    Pallas interpreter); else through the gathered dense view."""

    # -- compiled-program bodies ---------------------------------------
    def decode_chunk(self, W: int):
        cfg, paged, interpret = self.cfg, self._paged, self._interpret

        def step(params, tok, cache, tables, pos, live):
            logits, cache = llama.decode_step_rows(
                cfg, params, tok, cache, pos,
                tables=tables if paged else None, live=live,
                interpret=interpret)
            return logits, cache, ()

        return chunk_program(step, self.chunk,
                             gather=None if paged else self.kv)

    def prefill_packed(self, N: int):
        def forward(params, _state, tokens, packed, _slots):
            # garbage KV rows written for pad positions stay masked
            # (a row's pos starts at its prompt's length) and are
            # overwritten as decoding advances through them
            logits, kv = llama.forward(
                self.cfg, params, tokens, return_kv=True, packed=packed)
            return logits, kv, ()  # ks/vs [L, 1, N, KV, hd]

        return packed_prefill_program(self.kv, 0, forward,
                                      segmented=self.segmented)

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            # full-sequence logits: the real continuation logit lives
            # at position T-1
            logits, (ks, vs) = llama.forward(
                self.cfg, params, prompt, return_kv=True
            )
            return logits[0], ks, vs  # ks/vs [L, 1, bucket, KV, hd]

        return _pf

    def suffix_prefill(self, s_bucket: int, p_blocks: int):
        def _pf(params, *flat):
            *cache, suffix, blk_ids, prefix_len = flat
            logits, (ks, vs) = llama.forward_with_prefix(
                self.cfg, params, suffix, self.kv.rows(cache, blk_ids),
                prefix_len)
            return logits[0], ks, vs

        return _pf

    def kv_write(self, t_in: int, nb: int):
        target = nb * self.kv.block_size

        def fit(k1, v1):
            # k1/v1 [L, 1, t_in, KV, hd] -> exactly nb blocks
            if t_in < target:
                pad = [(0, 0), (0, 0), (0, target - t_in), (0, 0),
                       (0, 0)]
                return jnp.pad(k1, pad), jnp.pad(v1, pad)
            return k1[:, :, :target], v1[:, :, :target]

        return kv_write_program(self.kv, fit)


class _ExpertCounters:
    """What the two expert models share: the route's keywords, and the
    two counters of the expert layers the decode program hands back
    with its tokens (`aux_rows`; `self._pairs`: expert layers x routed
    experts)."""

    aux_rows = 2  # [experts_touched summed over the chunk | load_max]

    @staticmethod
    def _aux(stats):
        return jnp.stack([jnp.sum(stats[0]), jnp.max(stats[1])])

    def tick_fields(self, aux) -> Dict[str, object]:
        """`aux` [2, slots] from a harvested chunk: the decode steps'
        distinct (layer, expert) pairs, a step's mean over the chunk
        (of `self._pairs`), and the most rows any one expert got in any
        step and layer."""
        return {"experts_touched": float(aux[0, 0]) / self.chunk,
                "experts_total": self._pairs,
                "expert_load_max": int(aux[1, 0])}

    def _kw(self):
        # interpret mode walks the grouped product tile by tile in
        # Python: the CPU kernel tests take `lax.ragged_dot` instead
        return dict(kernel=self._paged and not self._interpret,
                    interpret=self._interpret)


class LatentMoeEngineModel(_ExpertCounters, _EngineModel):
    """`models/deepseek_v3.py` behind the seam: ONE latent pool
    `[L, num_blocks, block_size, Dp]` — a token and layer cache the
    normalised compressed KV beside the rotated shared key, 576 values
    (`Dp` is 576 rounded up to whole lanes, 640: see
    `ops/paged_attention.MLA_LANES`) and nothing per head.  Prefill
    (and the suffix prefill behind a cached prefix) expands the latents
    through `W_kvb`; decode is the absorbed form on the pool as it
    lies.  The decode program hands back two counters of the expert
    layers with its tokens (`aux_rows`).  `paged`: latent kernels +
    Pallas grouped products (TPU; `moe.row_tiling` says whose); else
    the dense view + `lax.ragged_dot` (anywhere)."""

    def __init__(self, cfg, kv: PagedKV, **route):
        super().__init__(cfg, kv, **route)
        self.width = kv.leaves[0].tail[0]
        self._pairs = cfg.n_moe_layers * cfg.n_routed_experts

    def decode_chunk(self, W: int):
        cfg, paged, kw = self.cfg, self._paged, self._kw()

        def step(params, tok, cache, tables, pos, live):
            logits, pool, st = deepseek_v3.decode_step(
                cfg, params, tok, cache[0], pos,
                tables=tables if paged else None, live=live, **kw)
            return logits, (pool,), (st["experts_touched"], st["load_max"])

        return chunk_program(step, self.chunk,
                             gather=None if paged else self.kv, aux=self._aux)

    def prefill_packed(self, N: int):
        def forward(params, _state, tokens, packed, _slots):
            logits, lat = deepseek_v3.forward(
                self.cfg, params, tokens, return_kv=True, packed=packed,
                **self._kw())  # lat [L, 1, N, 576]
            return logits, (lat,), ()

        def fit(lat):  # -> the pool's Dp columns
            return (jnp.pad(lat, ((0, 0),) * 3
                            + ((0, self.width - lat.shape[-1]),)),)

        return packed_prefill_program(self.kv, 0, forward, fit,
                                      segmented=self.segmented)

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            logits, lat = deepseek_v3.forward(
                self.cfg, params, prompt, return_kv=True, **self._kw())
            return logits[0], lat  # lat [L, 1, bucket, 576]

        return _pf

    def suffix_prefill(self, s_bucket: int, p_blocks: int):
        def _pf(params, pool, suffix, blk_ids, prefix_len):
            logits, lat = deepseek_v3.forward_with_prefix(
                self.cfg, params, suffix, self.kv.rows((pool,), blk_ids)[0],
                prefix_len, **self._kw())
            return logits[0], lat

        return _pf

    def kv_write(self, t_in: int, nb: int):
        target = nb * self.kv.block_size

        def fit(lat):
            # lat [L, 1, t_in, 576] -> exactly nb blocks of Dp columns
            lat = lat[:, 0, :target]
            return (jnp.pad(lat, ((0, 0), (0, target - lat.shape[1]),
                                  (0, self.width - lat.shape[2]))),)

        return kv_write_program(self.kv, fit)


class RetentionEngineModel(_EngineModel):
    """`models/brumby.py` behind the seam: every layer a power
    retention layer, the cache a `SlotState` of two float32 leaves
    (`ops/retention.state_shapes`): `state` `[L, slots, KV, d/2 + 1, d,
    d]` and `keysum` `[L, slots, KV, d/2 + 1, d]`.  Admission is a
    chunked scan over the packed row that leaves each prompt's state in
    its slot (`pack_align`: every prompt starts on a multiple of the
    scan's chunk, which is the engine's `block_size`); decode is one
    state READ a live row and step, and one state write a live row and
    chunk (`decode_chunk`).  `suffix_prefill` and `kv_write` do not
    exist: there is no cached prefix to prefill behind.  `paged`: the
    Pallas kernels (TPU); else the same algorithms in plain XLA."""

    def __init__(self, cfg, state: SlotState, *, pack_align: int, **route):
        super().__init__(cfg, None, state=state, **route)
        self.pack_align = pack_align
        self.state_write_deferred = self.chunk > 1

    def _kw(self):
        return dict(kernel=self._paged and not self._interpret,
                    interpret=self._interpret)

    def decode_chunk(self, W: int):
        """A chunk's steps READ each live row's state and hold the
        token's keys and values beside it (`retention.Pending`, made
        empty inside the program: it is no leaf of the cache); its LAST
        step folds them in and writes the state, once a chunk where a
        step a token would write it `chunk` times.  So between programs
        the two leaves are whole: prefill, harvest and any snapshot see
        states with nothing owed.  A row that dies inside the chunk is
        not flushed: its state is never read again (a prefill's first
        chunk zeroes the slot).  `chunk` 1 has nothing to defer."""
        cfg, kw, held = self.cfg, self._kw(), self.chunk - 1

        def step_of(last):
            def step(params, tok, cache, tables, pos, live):
                state, keysum, pend = cache
                logits, leaves, pend = brumby.chunk_step(
                    cfg, params, tok, (state, keysum), pend, pos, last=last,
                    live=live, **kw)
                return logits, (*leaves, pend), ()
            return step

        flush = step_of(True)
        program = (chunk_program(step_of(False), self.chunk, paged=False,
                                 last_step=flush)
                   if held else chunk_program(flush, 1, paged=False))

        def _fn(params, state, keysum, tok, pos, stop):
            pend = brumby.init_pending(cfg, tok.shape[0], held)
            state, keysum, _, *rows = program(params, state, keysum, pend,
                                              tok, pos, stop)
            return (state, keysum, *rows)

        return _fn

    def prefill_packed(self, N: int):
        def forward(params, state, tokens, packed, slots):
            logits, state = brumby.forward(
                self.cfg, params, tokens, state, packed=packed, slots=slots,
                chunk=self.pack_align, **self._kw())
            return logits, (), state

        return packed_prefill_program(None, len(self.state.leaves), forward)

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            logits, _ = brumby.forward(self.cfg, params, prompt,
                                       chunk=self.pack_align, **self._kw())
            return (logits[0],)

        return _pf

    suffix_prefill = kv_write = _EngineModel._no_prefix


class HybridEngineModel(_ExpertCounters, _EngineModel):
    """`models/lfm2.py` behind the seam: a cache of BOTH kinds.  Paged
    `k` and `v` `[attn_layers, num_blocks, block_size, KV, hd]` for the
    few attention layers (`kv`, a `PagedKV` whose leaves count those
    layers; a token's heads of 64 lie side by side in one row of whole
    lanes, `[.., KV * hd]`: `ops/paged_attention.kv_pool_tail`), and
    behind them a per-slot `conv` `[conv_layers, slots,
    conv_L * D]` for the convolution layers (`state`), in the compute
    dtype.  A request needs a slot AND its blocks; the packed prefill
    is handed `blk_ids` for the one kind and `slots` for the other (the
    forward leaves each prompt's convolution state in its slot); the
    decode chunk appends to and reads the pool through the tables and
    rolls each live row's state.  The decode program hands back the
    expert layers' two counters, as the latent model's (`aux_rows`).
    `suffix_prefill` and `kv_write` do not exist: sharing a prefix would
    need the convolution state at the block boundary.  `paged`: the
    paged kernels + Pallas grouped products (TPU); else the dense
    view + `lax.ragged_dot` (anywhere)."""

    def __init__(self, cfg, kv: PagedKV, state: SlotState, **route):
        super().__init__(cfg, kv, state=state, **route)
        self._pairs = cfg.n_moe_layers * cfg.n_experts

    def decode_chunk(self, W: int):
        cfg, paged, kw = self.cfg, self._paged, self._kw()

        def step(params, tok, cache, tables, pos, live):
            logits, cache, st = lfm2.decode_step(
                cfg, params, tok, cache, pos,
                tables=tables if paged else None, live=live, **kw)
            return logits, cache, (st["experts_touched"], st["load_max"])

        return chunk_program(step, self.chunk,
                             gather=None if paged else self.kv, aux=self._aux)

    def prefill_packed(self, N: int):
        def forward(params, state, tokens, packed, slots):
            logits, kv, conv = lfm2.forward(
                self.cfg, params, tokens, state[0], packed=packed,
                slots=slots, **self._kw())
            return logits, kv, (conv,)  # ks/vs [attn_layers, 1, N, KV, hd]

        return packed_prefill_program(self.kv, 1, forward,
                                      segmented=self.segmented)

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            logits, (ks, vs), _ = lfm2.forward(self.cfg, params, prompt,
                                               **self._kw())
            return logits[0], ks, vs

        return _pf

    suffix_prefill = kv_write = _EngineModel._no_prefix


class SparseLatentEngineModel(_ExpertCounters, _EngineModel):
    """`models/dots3.py` behind the seam: latent attention of TWO forms
    and a learned sparse selection, so THREE paged leaves of different
    widths and layer counts on one block table: `latent` `[full layers,
    .., 576 -> 640]`, `index_k` `[full layers, .., 128]` (the indexer's
    keys) and `swa_latent` `[window layers, .., 1088 -> 1152]`.  A
    window layer's rows lie on the sequence's table like the others and
    none is freed while the sequence lives; its programs READ only the
    blocks the window can touch (a start position), so its time is
    O(window).  Every leaf is paged, so the radix prefix cache shares
    all three.

    Both programs reach the pools through the table in plain XLA on
    any backend (no dense view, no route of its own for the CPU);
    `paged` only picks the experts' grouped products (Pallas on a TPU,
    `lax.ragged_dot` elsewhere).

    ONE ADMISSION FAMILY (`packs_suffixes`), `suffix_prefill_packed(N)`,
    printed `jit_suffix_prefill_packed_n<N>`: a selection makes every
    query's key set its own, so nothing is gained by one mask over
    several prompts, but everything by ONE READ OF THE WEIGHTS for a
    tick's admissions.  `(params, *cache, tokens [N], posn [N], tables
    [N / pack_align, W], last [K], slots [K], pos0 [K], stop0 [K], pos,
    tok, stop) -> (*cache, pos, tok, stop)`: up to `K` requests' next
    tokens end to end in a row of `N`, each from a multiple of
    `pack_align` (`dots3.QUERY_BLOCK` rows, or the largest power of two
    below it that the engine's sizes allow), at positions `posn` of
    their own sequences (-1: padding); a prefix hit starts behind its
    cached blocks, a miss at 0, a chunk of a long prompt behind the
    chunks before it.  Each query block of `pack_align` rows carries
    its sequence's whole row of the block table (`W` the engine's
    `_max_seq_blocks`: the program's shape knows nothing of a prefix's
    length), a layer writes the new rows into the requests' own blocks
    and then attends through the table as the decode step does, and
    only the query blocks that hold a token are walked
    (`dots3.forward_with_prefix`).  The head runs on the `K` rows
    `last` names, the first tokens are picked inside and the admitted
    slots' state set (a chunk that is not its prompt's last names a
    slot past the last: dropped), as `packed_prefill_program` does.
    `N` comes from the engine's closed set (`_pack_sizes`: 128, 256,
    512, 1,024 and `prefill_chunk`), all of it warmed at start.  The
    decode program hands back the HELD experts' two counters
    (`aux_rows`)."""

    packs_suffixes = True
    pack_align = dots3.QUERY_BLOCK

    def __init__(self, cfg, kv: PagedKV, **route):
        super().__init__(cfg, kv, **route)
        self._pairs = cfg.n_moe_layers * cfg.experts_held

    def tick_fields(self, aux) -> Dict[str, object]:
        return {**super().tick_fields(aux),
                "experts_held": self.cfg.experts_held}

    def context_fields(self, contexts: Sequence[int]) -> Dict[str, object]:
        """`dsa_selected_share`: the mean over the live rows of the
        share of its context a full layer attends, `min(T, index_topk)
        / T`; `window_rows_live`: the rows a window layer reads for
        them, `min(T, window)` each."""
        if not contexts:
            return {"dsa_selected_share": 0.0, "window_rows_live": 0}
        k, w = self.cfg.index_topk, self.cfg.window
        return {"dsa_selected_share": sum(min(t, k) / t for t in contexts)
                / len(contexts),
                "window_rows_live": sum(min(t, w) for t in contexts)}

    def decode_chunk(self, W: int):
        cfg, kw = self.cfg, self._kw()

        def step(params, tok, cache, tables, pos, live):
            logits, cache, st = dots3.decode_step(
                cfg, params, tok, cache, pos, tables, live=live, **kw)
            return logits, cache, (st["experts_touched"], st["load_max"])

        return chunk_program(step, self.chunk, aux=self._aux)

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            # the rows stay in the call's own cache
            return (dots3.forward(self.cfg, params, prompt[0], **self._kw()),)

        return _pf

    def suffix_prefill_packed(self, N: int):
        cfg, kw, n = self.cfg, self._kw(), len(self.kv.leaves)

        def _fn(params, *flat):
            cache = flat[:n]
            (tokens, posn, tables, last, slots, pos0, stop0,
             pos, tok, stop) = flat[n:]
            logits, cache, _ = dots3.forward_with_prefix(
                cfg, params, tokens, posn, cache, tables, last=last, **kw)
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (*cache,
                    *_admitted(pos, tok, stop, slots, pos0, tok0, stop0))

        return _fn


class _CarriedStateAdmission:
    """The THREE admission programs of a model whose per-slot leaves a
    long prompt's chunks CARRY (`state_carries_chunks`), over
    `self._module`'s `forward` and `forward_chunk`:
    `prefill_packed(N)`, whole prompts end to end, each prompt's end
    state left in its slot by the forward itself; `prefill(bucket)`;
    and `chunk_prefill(N)`, printed `jit_prefill_chunk_n<N>`: tokens `lo
    .. lo + n` of ONE prompt too long for a packed program, `(params,
    *cache, tokens [N], table [W], slot, lo, n, admit, pos0, stop0, pos,
    tok, stop) -> (*cache, pos, tok, stop)`, which takes the whole
    cache, the slot's leaves beside the request's blocks, and hands it
    back; `admit` is the slot whose `pos` / `tok` / `stop` the chunk
    sets (the prompt's last chunk; any other names a slot past the last:
    dropped).  `suffix_prefill` and `kv_write` do not exist: sharing a
    prefix would need the per-slot leaves at the block boundary."""

    state_carries_chunks = True

    def _kw(self):
        # the paged layers' attention follows the route in all three
        # programs: the paged decode kernels, the fused prefill fold
        return dict(super()._kw(), paged_kernel=self._paged)

    def prefill_packed(self, N: int):
        def forward(params, state, tokens, packed, slots):
            # -> (logits, (ks, vs) [paged layers, 1, N, KV * d], state)
            return self._module.forward(
                self.cfg, params, tokens, state, packed=packed, slots=slots,
                **self._kw())

        return packed_prefill_program(self.kv, len(self.state.leaves),
                                      forward, segmented=self.segmented)

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            logits, (ks, vs), _ = self._module.forward(
                self.cfg, params, prompt, **self._kw())
            return logits[0], ks, vs

        return _pf

    def chunk_prefill(self, N: int):
        cfg, kw, n = self.cfg, self._kw(), len(self.cache_leaves)
        forward_chunk = self._module.forward_chunk

        def _fn(params, *flat):
            cache = flat[:n]
            (tokens, table, slot, lo, real, admit, pos0, stop0,
             pos, tok, stop) = flat[n:]
            logits, cache = forward_chunk(
                cfg, params, tokens, lo, real, cache, table, slot, **kw)
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (*cache,
                    *_admitted(pos, tok, stop, admit, pos0, tok0, stop0))

        return _fn

    suffix_prefill = kv_write = _EngineModel._no_prefix


class WindowFullEngineModel(_CarriedStateAdmission, _ExpertCounters,
                            _EngineModel):
    """`models/mimo_v2.py` behind the seam: a cache of BOTH kinds, both
    for ATTENTION.  Paged `k` `[full layers, num_blocks, block_size, KV
    * 192]` and `v` `[.., KV * 128]` for the full layers (`kv`: the two
    tails DIFFER, a token's heads folded side by side into whole lanes),
    and behind them a per-slot RING of window rows for the window
    layers, `swa_k` `[window layers, slots, ring, KV_w * 192]` and
    `swa_v` `[.., KV_w * 128]` (`state`): the row of position `p` at `p
    mod ring`, `ring` the window.  A decode step appends to and reads
    the pools through the tables, writes its row into each live slot's
    ring and reads the ring masked by position.  No row of a window
    layer is paged: `cache_bytes_per_token` counts the full layers alone
    and `cache_bytes_per_slot`, the rings, does not grow with a
    sequence; admission needs a slot and the FULL layers' blocks.

    THREE admission programs.  `prefill_packed(N)`: whole prompts end to
    end under the segment, causal and window masks, each prompt's last
    `ring` window rows left in its slot's ring.  `chunk_prefill(N)`,
    printed `jit_prefill_chunk_n<N>`: tokens `lo .. lo + n` of ONE
    prompt too long for a packed program, `(params, *cache, tokens [N],
    table [W], slot, lo, n, admit, pos0, stop0, pos, tok, stop) ->
    (*cache, pos, tok, stop)`: its full layers write the chunk's rows
    into the request's blocks and attend the earlier chunks' through
    `table` key block by key block; its window layers attend the slot's
    ring beside the chunk and leave the ring at the chunk's end
    (`state_carries_chunks`); `admit` is the slot whose `pos` / `tok` /
    `stop` the chunk sets (the prompt's last chunk; any other names a
    slot past the last: dropped).  `suffix_prefill` and `kv_write` do
    not exist: sharing a prefix would need the ring at the block
    boundary.  `paged`: the paged decode kernels, the full layers'
    prefill attention as one fused kernel a layer in both admission
    programs (`ops/prefill_attention.py`: the scores stay in VMEM) +
    Pallas grouped products (TPU; `interpret`: the attention kernels in
    the interpreter); else the same through the table in plain XLA,
    the prefill's running softmax a `fori_loop` over key blocks, +
    `lax.ragged_dot` (anywhere)."""

    _module = mimo_v2

    def __init__(self, cfg, kv: PagedKV, state: SlotState, **route):
        super().__init__(cfg, kv, state=state, **route)
        self._pairs = cfg.n_moe_layers * cfg.experts_held
        # what `stats()["cache_bytes_per_slot"]` reads: a slot's rings
        self._ring_bytes = BlockPool(2, spec=state.leaves).bytes_per_slot(0)

    def tick_fields(self, aux) -> Dict[str, object]:
        return {**super().tick_fields(aux),
                "experts_held": self.cfg.experts_held}

    def context_fields(self, contexts: Sequence[int]) -> Dict[str, object]:
        """Of the rows live at a chunk's first step: `window_rows_live`,
        the ring rows a window layer reads for them (`min(T, window)`
        each); `ring_bytes_live`, their rings' bytes over the window
        layers; `full_cache_tokens_live`, the tokens a full layer reads
        for them."""
        w = self.cfg.window
        return {"window_rows_live": sum(min(t, w) for t in contexts),
                "ring_bytes_live": len(contexts) * self._ring_bytes,
                "full_cache_tokens_live": sum(contexts)}

    def decode_chunk(self, W: int):
        cfg, kw = self.cfg, self._kw()

        def step(params, tok, cache, tables, pos, live):
            logits, cache, st = mimo_v2.decode_step(
                cfg, params, tok, cache, pos, tables, live=live, **kw)
            return logits, cache, (st["experts_touched"], st["load_max"])

        return chunk_program(step, self.chunk, aux=self._aux)


class BlockDiffusionEngineModel(_ExpertCounters, _EngineModel):
    """`models/sdar.py` behind the seam: a model that generates by
    DIFFUSION OVER BLOCKS of `B = cfg.block_length` positions, every
    layer an expert layer (softmax top-k).  Folded K and V pools `[L,
    num_blocks, block_size, KV * hd]` (`kv`), `block_size % B == 0`, so
    a block never straddles two pool blocks.

    A forward yields 0 or `B` tokens a row, and how many forwards a
    block takes depends on the request (`denoising_steps`, `confidence_
    threshold`: `request_fields`) and on the logits, so everything the
    engine counted on the host for a one-token model comes FROM THE
    DEVICE here (`device_counts`): `decode_chunk` is `block_chunk_
    program`, whose `toks` say per slot how many tokens the chunk
    output, which, the step each was decided at and the forwards the
    row was live in (`harvested`); `tick_fields` hands the tick ring its
    counters.  A row's device state beside `pos` and `stop` is a
    `BlockRows` row (`init_tok`).  Admission (`prefill_packed`) prefills
    a prompt's whole blocks under the block-causal mask with NO head and
    no token: its `first` makes the row's first block from the prompt's
    `T mod B` tail tokens, `pos` the tail's start, nothing pending.
    `stop` is `rows_needed`: `ceil((T + n) / B) B`.

    A block is output by the forward that decides its last position,
    and its clean K and V rows are written by the row's NEXT forward,
    which carries them as a commit half beside the next block's first
    denoising forward (`block_chunk_program`): a block takes `S`
    forwards, not `S + 1`, and a chunk of `chunk` forwards may output
    `chunk` blocks a row (`advance`).  A request's last block is output
    and never committed: nothing reads its rows.

    `suffix_prefill` and `kv_write` do not exist: a prefix would be
    valid at multiples of `B`, which no test holds yet, so the prefix
    cache is refused; one over this model would also have to commit a
    request's last block before adopting it.  `paged`: the paged kernels
    (`B` rows appended a slot, `B x H` query heads of one row, each
    called once a half) + Pallas grouped products (TPU); else the dense
    view + `lax.ragged_dot` (anywhere).
    `commit` False is the benchmark's control, which that sets on the
    class before it makes its engine and nothing else does: no commit is
    ever pending, no forward carries a commit half, and a block's rows
    stay as its last denoising forward wrote them, masks in its input."""

    aux_rows = 7
    device_counts = True
    shares_prefix = False
    commit = True
    summed = ("fused_commit_row_steps",)

    def __init__(self, cfg, kv: PagedKV, **route):
        super().__init__(cfg, kv, **route)
        B = cfg.block_length
        self.rows = BlockRows(B)
        self._pairs = cfg.n_layers * cfg.n_experts
        # a forward may output a block, and writes no row past it
        self.advance = self.reach = self.chunk * B
        self.token_rows = 2 + 2 * self.advance

    # -- what a request is to this model --------------------------------
    def init_tok(self, slots: int) -> jax.Array:
        return jnp.zeros((slots, self.rows.width), jnp.int32)

    def rows_needed(self, T: int, n_new: int) -> int:
        B = self.rows.block
        return -(-(T + n_new) // B) * B

    def first_pos(self, T: int) -> int:
        return T - T % self.rows.block

    def request_fields(self, denoising_steps=None,
                       confidence_threshold=None) -> Dict[str, object]:
        B = self.rows.block
        steps = (self.cfg.denoising_steps if denoising_steps is None
                 else int(denoising_steps))
        thr = (self.cfg.confidence_threshold if confidence_threshold is None
               else float(confidence_threshold))
        if not 1 <= steps <= B:
            raise ValueError(f"denoising_steps={steps} not in [1, {B}]")
        if not 0.0 <= thr:
            raise ValueError(f"confidence_threshold={thr} is negative")
        return {"denoising_steps": steps, "confidence_threshold": thr}

    def pack_extras(self, K: int, reqs: Sequence[Dict]) -> tuple:
        import numpy as np

        steps, thr = np.ones(K, np.int32), np.zeros(K, np.float32)
        for i, req in enumerate(reqs):
            steps[i] = req["fields"]["denoising_steps"]
            thr[i] = req["fields"]["confidence_threshold"]
        return steps, thr

    def harvested(self, toks_host, slot: int, first: bool) -> tuple:
        n, cap = int(toks_host[0, slot]), self.advance
        return toks_host[2:2 + n, slot], {
            "decided_at": toks_host[2 + cap:2 + cap + n, slot],
            "forwards": int(toks_host[1, slot])}

    @staticmethod
    def _aux(stats):
        *counted, touched, load = stats
        return jnp.stack([*(jnp.sum(c) for c in counted), jnp.sum(touched),
                          jnp.max(load)])

    def tick_fields(self, aux) -> Dict[str, object]:
        """`aux` [7, slots] from a harvested chunk: its ROW-FORWARDS, a
        live (slot, forward) pair each (what this model's
        `row_steps_live` counts), by kind: `denoise_row_steps` decided
        positions, `commit_row_steps` decided NOTHING (0: a commit is
        no forward of its own; the key stays, its reader asks for it);
        `fused_commit_row_steps`, those that carried a pending commit
        beside their own block; `tokens_committed`, the tokens OUTPUT;
        `attended_tokens`, the cached columns ONE CALL of the attention
        kernel read, a forward's mean: a forward is one call a half,
        the open halves' (`pos + B` a live row) and the commit halves'
        (`pos` where one rode), so both halves' columns, halved (the
        control's forward has one half: not halved); then the experts'
        counters as `_ExpertCounters` gives them (a forward's mean too,
        both halves' experts)."""
        idle, denoise, attended, fused, blocks = (
            int(aux[i, 0]) for i in range(5))
        return {"row_steps_live": idle + denoise,
                "commit_row_steps": idle, "denoise_row_steps": denoise,
                "fused_commit_row_steps": fused,
                "tokens_committed": blocks * self.rows.block,
                "attended_tokens": attended / self.chunk / (
                    2 if self.commit else 1),
                **super().tick_fields(aux[5:])}

    # -- compiled-program bodies ---------------------------------------
    def decode_chunk(self, W: int):
        cfg, paged, kw = self.cfg, self._paged, self._kw()

        def step(params, tokens, cache, tables, pos, live, commit):
            logits, cache, st = sdar.block_step(
                cfg, params, tokens, cache, pos,
                tables=tables if paged else None, live=live, commit=commit,
                **kw)
            return logits, cache, (st["experts_touched"], st["load_max"])

        return block_chunk_program(
            step, sdar.unmask, self.chunk, self.rows, cfg.mask_id,
            self.advance, gather=None if paged else self.kv, aux=self._aux,
            commit=self.commit)

    def prefill_packed(self, N: int):
        B, rows = self.rows.block, self.rows

        def forward(params, _state, tokens, packed, _slots):
            # a tail's rows (`T mod B` tokens of a block that is not
            # whole) are written too: no whole block sees them, and the
            # block's first forward writes them again
            _, kv = sdar.forward(self.cfg, params, tokens, packed=packed,
                                 logits=False, **self._kw())
            return None, kv, ()  # ks / vs [L, 1, N, KV * hd]

        def first(_logits, tokens, last, T, steps, thr):
            """Each prompt's first block: its `T mod B` tail tokens
            decided (by the prompt: -1), the rest open, from the tail's
            start."""
            tail = T % B
            j = jnp.arange(B, dtype=jnp.int32)[None, :]
            und = j >= tail[:, None]
            at = jnp.clip(last[:, None] - tail[:, None] + 1 + j, 0,
                          tokens.shape[0] - 1)
            blk = jnp.where(und, 0, tokens[at])
            return T - tail, rows.pack(
                blk, und, jnp.full_like(blk, -1), jnp.zeros_like(T), steps,
                thr)

        return packed_prefill_program(self.kv, 0, forward,
                                      segmented=self.segmented, first=first,
                                      extras=2)

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            logits, (ks, vs) = sdar.forward(self.cfg, params, prompt,
                                            **self._kw())
            return logits[0], ks, vs

        return _pf

    suffix_prefill = kv_write = _EngineModel._no_prefix


class RecurrentEngineModel(_CarriedStateAdmission, _ExpertCounters,
                           _EngineModel):
    """`models/nemotron_h.py` behind the seam: a cache of THREE leaves
    over DISJOINT layers.  Paged `k` and `v` `[attention layers,
    num_blocks, block_size, KV * hd]` for the few attention layers
    (`kv`; a token's heads folded side by side into whole lanes), and
    behind them, for the Mamba-2 layers, a per-slot RECURRENT state
    `ssm` `[Mamba layers, slots, heads, head_dim, N]` float32 and the
    convolution's last inputs `conv` `[.., slots, (taps - 1) *
    conv_dim]` in the compute dtype (`state`).  The expert layers hold
    nothing.  `cache_bytes_per_token` counts the attention layers alone
    and `cache_bytes_per_slot`, the states, does not grow with a
    sequence; admission needs a slot and the attention layers' blocks.

    THREE admission programs, as `WindowFullEngineModel`'s.
    `prefill_packed(N)`: whole prompts end to end, the scan and the
    convolution RESET at a segment's start, each prompt's end state left
    in its slot.  `chunk_prefill(N)`, printed `jit_prefill_chunk_n<N>`,
    in `WindowFullEngineModel`'s signature: tokens `lo .. lo + n` of ONE
    prompt too long for a packed program; its Mamba layers START FROM
    THE SLOT'S `ssm` and `conv` (zero where `lo` is 0, whatever the slot
    held) and leave them as they stand at the chunk's end
    (`state_carries_chunks`: a recurrent state a prefill RESUMES from),
    its attention layers write the chunk's rows into the request's
    blocks and attend the earlier chunks' through `table`.  A decode
    step steps every live row's state once, read and written in float32
    (a dead row's state stays as it was).  `suffix_prefill` and
    `kv_write` do not exist: sharing a prefix would need the states at
    the block boundary.  `paged`: the paged decode kernels, the fused
    prefill attention + Pallas grouped products (TPU); else the same in
    plain XLA + `lax.ragged_dot` (anywhere).  The scan of admission
    follows the same flags (`ops/ssd.ssd_scan(kernel=)`: one Pallas call
    a Mamba layer in both admission programs on the TPU, plain XLA
    elsewhere); the decode step's recurrence and the convolution are
    plain XLA on both routes.

    The decode program hands back THREE counters of the held experts
    (`aux_rows`): `_ExpertCounters`' two and `held_pairs`, the (row,
    expert) pairs that reached an expert this chip holds, summed over
    the chunk's steps and the expert layers."""

    aux_rows = 3
    _module = nemotron_h

    def __init__(self, cfg, kv: PagedKV, state: SlotState, **route):
        super().__init__(cfg, kv, state=state, **route)
        self._pairs = cfg.n_moe_layers * cfg.experts_held
        # what `stats()["cache_bytes_per_slot"]` reads: a slot's states
        self._slot_bytes = BlockPool(2, spec=state.leaves).bytes_per_slot(0)

    @staticmethod
    def _aux(stats):
        return jnp.stack([jnp.sum(stats[0]), jnp.max(stats[1]),
                          jnp.sum(stats[2])])

    def tick_fields(self, aux) -> Dict[str, object]:
        return {**super().tick_fields(aux),
                "experts_held": self.cfg.experts_held,
                "held_pairs": int(aux[2, 0])}

    def context_fields(self, contexts: Sequence[int]) -> Dict[str, object]:
        """Of the rows live at a chunk's first step: `ssm_bytes_live`,
        their states' bytes over the Mamba layers (whatever their
        contexts); `full_cache_tokens_live`, the tokens an attention
        layer reads for them."""
        return {"ssm_bytes_live": len(contexts) * self._slot_bytes,
                "full_cache_tokens_live": sum(contexts)}

    def decode_chunk(self, W: int):
        cfg, kw = self.cfg, self._kw()

        def step(params, tok, cache, tables, pos, live):
            logits, cache, st = nemotron_h.decode_step(
                cfg, params, tok, cache, pos, tables, live=live, **kw)
            return logits, cache, (st["experts_touched"], st["load_max"],
                                   st["held_pairs"])

        return chunk_program(step, self.chunk, aux=self._aux)


def engine_model_for(cfg, *, kv_dtype: str, block_size: int, **route):
    """The implementer for a model's config, its cache in the format
    the user's `kv_dtype` names: the model picks its route."""
    if isinstance(cfg, sdar.SdarMoeConfig):
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' is not wired for a block-diffusion cache: "
                "its K and V are folded pools with no scales, and a "
                "block's rows are written again at every forward")
        if block_size % cfg.block_length:
            raise ValueError(
                f"block_size={block_size} must be whole blocks of "
                f"block_length={cfg.block_length}: a block's rows lie in "
                "one pool block")
        tail = (cfg.n_kv_heads * cfg.head_dim,)
        return BlockDiffusionEngineModel(cfg, PagedKV(
            {"k": tail, "v": tail}, cfg.dtype, block_size, kv_dtype), **route)
    if isinstance(cfg, nemotron_h.NemotronHConfig):
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' is not wired for the recurrent cache: its "
                "K and V are one layer in eleven, 1 KB a token already, and "
                "the state is float32 by the model's own statement")
        tail = (cfg.n_kv_heads * cfg.head_dim,)
        return RecurrentEngineModel(
            cfg, PagedKV({"k": tail, "v": tail}, cfg.dtype, block_size,
                         kv_dtype, layers=cfg.n_attn_layers),
            SlotState({"ssm": ((cfg.mamba_heads, cfg.mamba_head_dim,
                                cfg.state_size), jnp.float32),
                       "conv": (((cfg.conv_kernel - 1) * cfg.conv_dim,),
                                cfg.dtype)},
                      layers=cfg.n_mamba_layers), **route)
    if isinstance(cfg, mimo_v2.MimoV2Config):
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' is not wired for the window-and-full "
                "cache: its paged K and V are folded pools of two widths "
                "with no scales, and the rings are 3 MB a slot already")
        if cfg.full_sink and route.get("paged"):
            raise ValueError("the paged decode kernel has no sink column; "
                             "a full layer with a sink needs "
                             "decode_kernel='gather'")
        dk, dv = cfg.head_dim, cfg.v_head_dim
        return WindowFullEngineModel(
            cfg, PagedKV({"k": (cfg.n_kv_heads * dk,),
                          "v": (cfg.n_kv_heads * dv,)}, cfg.dtype,
                         block_size, kv_dtype, layers=cfg.n_full_layers),
            SlotState({"swa_k": ((cfg.ring_rows, cfg.swa_n_kv_heads * dk),
                                 cfg.dtype),
                       "swa_v": ((cfg.ring_rows, cfg.swa_n_kv_heads * dv),
                                 cfg.dtype)},
                      layers=cfg.n_swa_layers), **route)
    if isinstance(cfg, dots3.Dots3Config):
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' quantizes per-head K and V rows; the "
                "latent pools have no heads to scale by")
        return SparseLatentEngineModel(cfg, PagedKV(
            {"latent": (_pa.mla_pool_width(cfg.latent_dim),),
             "index_k": (cfg.index_head_dim,),
             "swa_latent": (_pa.mla_pool_width(cfg.swa_latent_dim),)},
            cfg.dtype, block_size, kv_dtype,
            used={"latent": cfg.latent_dim,
                  "swa_latent": cfg.swa_latent_dim},
            layers={"latent": cfg.n_full_layers,
                    "index_k": cfg.n_full_layers,
                    "swa_latent": cfg.n_swa_layers}), **route)
    if isinstance(cfg, brumby.BrumbyConfig):
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' quantizes per-token K and V rows; a "
                "retention state is one float32 accumulator a sequence")
        state, keysum = _ret.state_shapes(0, 0, cfg.n_kv_heads, cfg.head_dim)
        return RetentionEngineModel(cfg, SlotState(
            {"state": (state[2:], jnp.float32),
             "keysum": (keysum[2:], jnp.float32)}),
            pack_align=block_size, **route)
    if isinstance(cfg, lfm2.Lfm2MoeConfig):
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' is not wired for the hybrid cache: its "
                "K and V are 4 of 16 layers' and 8 KB a token already")
        tail = _pa.kv_pool_tail(cfg.n_kv_heads, cfg.head_dim)
        return HybridEngineModel(
            cfg, PagedKV({"k": tail, "v": tail}, cfg.dtype, block_size,
                         kv_dtype, layers=cfg.n_attn_layers),
            SlotState({"conv": ((cfg.conv_L * cfg.dim,), cfg.dtype)},
                      layers=cfg.n_conv_layers), **route)
    if isinstance(cfg, deepseek_v3.DeepseekV3Config):
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' quantizes per-head K and V rows; the "
                "latent pool has no heads to scale by")
        width = _pa.mla_pool_width(cfg.latent_dim)
        return LatentMoeEngineModel(cfg, PagedKV(
            {"latent": (width,)}, cfg.dtype, block_size, kv_dtype,
            used=cfg.latent_dim), **route)
    tail = (cfg.n_kv_heads, cfg.head_dim)
    return LlamaEngineModel(cfg, PagedKV(
        {"k": tail, "v": tail}, cfg.dtype, block_size, kv_dtype), **route)
