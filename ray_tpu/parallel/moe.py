"""Mixture-of-experts with expert parallelism over the `ep` mesh axis.

Absent from the reference (SURVEY §2.5: EP/MoE "Absent — build: expert
mesh axis + ragged all-to-all").  Design: top-k token routing with a
capacity factor; tokens are dispatched to their experts' devices with
`lax.all_to_all` over `ep` inside `shard_map`, each device runs its
resident experts' FFN as one batched matmul (MXU-friendly fixed
capacity slots — dropped tokens pass through the residual), results
return via the inverse all-to-all and combine weighted by router probs.

Which expert layer does what: `moe_forward` (capacity slots, softmax
router, ungated FFN, a token over capacity is dropped) is the one that
TRAINS (`models/mixtral.py`).  `dropless_moe` at the end of this file
is the one that SERVES (`models/deepseek_v3.py` through the engine):
sigmoid scores, every token reaches all of its experts whatever the
load, SwiGLU experts as grouped products over tokens sorted by expert.
Do not take one for the other.  `dropless_moe_train` below it is the
serving layer's held share (`held=`) with a backward: the same pick,
the same weights, no pair dropped, what absent experts would add left
out (`models/afmoe.py` trains through it).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental.shard_map import shard_map


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int
    hidden: int
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16


def init_moe(cfg: MoEConfig, key: jax.Array) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    E, Dm, Dh = cfg.num_experts, cfg.dim, cfg.hidden
    std = 0.02
    return {
        "router": jax.random.normal(k1, (Dm, E), jnp.float32) * std,
        "w_in": jax.random.normal(k2, (E, Dm, Dh), jnp.float32) * std,
        "w_out": jax.random.normal(k3, (E, Dh, Dm), jnp.float32) * std,
    }


def moe_logical_axes(cfg: MoEConfig) -> Dict:
    return {
        "router": ("embed", None),
        "w_in": ("expert", "embed", "mlp"),
        "w_out": ("expert", "mlp", "embed"),
    }


def _capacity(tokens_per_device: int, cfg: MoEConfig, ep: int) -> int:
    cap = int(cfg.capacity_factor * tokens_per_device * cfg.top_k
              / cfg.num_experts)
    return max(cap, 4)


def moe_forward(cfg: MoEConfig, params: Dict, x: jax.Array,
                mesh: Optional[Mesh] = None) -> Tuple[jax.Array, Dict]:
    """x [B, T, D] -> (out [B, T, D], aux {load_balance_loss}).

    Without a mesh (or ep=1) this is the single-device dense-dispatch
    path; with an `ep` axis the same math runs under shard_map with
    all_to_all token exchange.
    """
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        return _moe_forward_ep(cfg, params, x, mesh)
    return _moe_forward_local(cfg, params, x)


def _route(cfg: MoEConfig, router_w, x2d):
    """Top-k routing; returns (probs [N, k], idx [N, k], aux loss)."""
    logits = (x2d.astype(jnp.float32) @ router_w)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # Switch-style load-balance loss: frac of tokens per expert x mean prob
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(top_i[:, 0], cfg.num_experts, dtype=jnp.float32), axis=0
    )
    aux = cfg.num_experts * jnp.sum(me * ce)
    return top_p, top_i, aux


def _expert_ffn(cfg: MoEConfig, w_in, w_out, slots):
    """slots [E_local, C, D] -> [E_local, C, D]; one batched matmul per
    projection (the MXU-friendly shape)."""
    h = jnp.einsum("ecd,edh->ech", slots.astype(cfg.dtype),
                   w_in.astype(cfg.dtype))
    h = jax.nn.gelu(h)
    return jnp.einsum("ech,ehd->ecd", h, w_out.astype(cfg.dtype))


def _dispatch(cfg: MoEConfig, x2d, top_p, top_i, capacity: int):
    """Build fixed-capacity expert slots.  Returns (slots [E, C, D],
    slot_pos [N, k], keep [N, k]); combine weights come from the router
    probs in _combine."""
    N = x2d.shape[0]
    E, C = cfg.num_experts, capacity
    # position of each (token, k) within its expert's slot list
    flat_i = top_i.reshape(-1)  # [N*k]
    one_hot = jax.nn.one_hot(flat_i, E, dtype=jnp.int32)  # [N*k, E]
    pos_in_expert = jnp.cumsum(one_hot, axis=0) - one_hot
    slot = jnp.sum(pos_in_expert * one_hot, axis=-1)  # [N*k]
    keep = slot < C
    slots = jnp.zeros((E, C, x2d.shape[1]), x2d.dtype)
    flat_tok = jnp.repeat(jnp.arange(N), cfg.top_k)
    slots = slots.at[
        jnp.where(keep, flat_i, 0), jnp.where(keep, slot, 0)
    ].add(jnp.where(keep[:, None], x2d[flat_tok], 0))
    return slots, slot.reshape(N, cfg.top_k), keep.reshape(N, cfg.top_k)


def _combine(cfg: MoEConfig, out_slots, top_p, top_i, slot_pos, keep, N):
    flat_i = top_i.reshape(-1)
    flat_s = slot_pos.reshape(-1)
    flat_keep = keep.reshape(-1)
    gathered = out_slots[flat_i, flat_s]  # [N*k, D]
    gathered = jnp.where(flat_keep[:, None], gathered, 0)
    weighted = gathered * top_p.reshape(-1)[:, None].astype(gathered.dtype)
    return weighted.reshape(N, cfg.top_k, -1).sum(axis=1)


def _moe_forward_local(cfg: MoEConfig, params: Dict, x: jax.Array):
    B, T, D = x.shape
    x2d = x.reshape(B * T, D)
    top_p, top_i, aux = _route(cfg, params["router"], x2d)
    cap = _capacity(B * T, cfg, ep=1)
    slots, slot_pos, keep = _dispatch(cfg, x2d, top_p, top_i, cap)
    out_slots = _expert_ffn(cfg, params["w_in"], params["w_out"], slots)
    out = _combine(cfg, out_slots, top_p, top_i, slot_pos, keep, B * T)
    return out.reshape(B, T, D).astype(x.dtype), {"load_balance_loss": aux}


def _moe_forward_ep(cfg: MoEConfig, params: Dict, x: jax.Array, mesh: Mesh):
    ep = mesh.shape["ep"]
    assert cfg.num_experts % ep == 0, "num_experts must divide ep"
    e_local = cfg.num_experts // ep

    def body(router_w, w_in, w_out, xs):
        # xs: this device's token shard [b, T, D]
        b, T, D = xs.shape
        x2d = xs.reshape(b * T, D)
        top_p, top_i, aux = _route(cfg, router_w, x2d)
        cap = _capacity(b * T, cfg, ep)
        slots, slot_pos, keep = _dispatch(cfg, x2d, top_p, top_i, cap)
        # slots [E, C, D] -> exchange: each device keeps rows for its
        # resident experts from EVERY peer: [E, C, D] -> [ep, e_local, C, D]
        slots = slots.reshape(ep, e_local, cap, D)
        # all_to_all over ep: axis 0 splits, results concatenate on a
        # new leading axis -> [ep(peers), e_local, C, D]
        recv = lax.all_to_all(slots, "ep", split_axis=0, concat_axis=0,
                              tiled=False)
        # run resident experts over all peers' tokens: fold the peer dim
        # into capacity so each resident expert runs ONE matmul over
        # peer*C rows — no weight replication
        peer, el = recv.shape[0], recv.shape[1]
        stacked = recv.transpose(1, 0, 2, 3).reshape(el, peer * cap, D)
        out = _expert_ffn(cfg, w_in, w_out, stacked)
        out = out.reshape(el, peer, cap, D).transpose(1, 0, 2, 3)
        # return to owners: inverse all_to_all
        back = lax.all_to_all(out, "ep", split_axis=0, concat_axis=0,
                              tiled=False)
        out_slots = back.reshape(cfg.num_experts, cap, D)
        combined = _combine(cfg, out_slots, top_p, top_i, slot_pos, keep,
                            b * T)
        return combined.reshape(b, T, D).astype(xs.dtype), aux.reshape(1)

    in_specs = (
        P(), P("ep"), P("ep"),  # router replicated; experts sharded on ep
        P("ep"),  # tokens sharded over ep (data-parallel style)
    )
    out_specs = (P("ep"), P("ep"))
    fn = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_rep=False,
    )
    out, aux = fn(params["router"], params["w_in"], params["w_out"], x)
    return out, {"load_balance_loss": jnp.mean(aux)}


# ----------------------------------------------------------------------
# the expert layer that serves: dropless, one chip
# ----------------------------------------------------------------------
def sigmoid_topk_route(h, w_router, bias, top_k: int, scale: float,
                       eps: float):
    """DeepSeek-V3's `noaux_tc` router with one group: scores
    `sigmoid(h W_g)` in float32 (matmul precision `highest`: a TPU's
    default would round the operands to bfloat16), the top `top_k` of
    `scores + bias` chosen, the weights taken from the scores WITHOUT
    the bias, normalised (`sum + eps`: the caller's model states it,
    1e-20 for `deepseek_v3`, 1e-6 for `lfm2_moe`) and scaled.  h [N, D]
    -> (weights [N, k] f32, experts [N, k] int32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision="highest"))
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scale
    return w, idx.astype(jnp.int32)


def softmax_topk_route(h, w_router, top_k: int, renorm: bool = True):
    """Qwen3-MoE's router (`sdar_moe` has it): probabilities `softmax(h
    W_g)` over ALL experts in float32 (matmul precision `highest`, as
    `sigmoid_topk_route`), the `top_k` largest chosen, and with `renorm`
    (`norm_topk_prob`) the chosen weights divided by their sum; no
    bias, no scale.  h [N, D] -> (weights [N, k] f32, experts [N, k]
    int32)."""
    probs = jax.nn.softmax(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision="highest"), axis=-1)
    w, idx = lax.top_k(probs, top_k)
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


# the routes `dropless_moe` knows by name: `(h, layer, top_k, scale,
# route_eps) -> (weights [N, k] f32, experts [N, k] int32)`
ROUTES = {
    "sigmoid": lambda h, layer, top_k, scale, eps: sigmoid_topk_route(
        h, layer["router"], layer["router_bias"], top_k, scale, eps),
    # `route_eps` unused: a softmax's top-k sums to more than 1 / E
    "softmax": lambda h, layer, top_k, scale, eps: softmax_topk_route(
        h, layer["router"], top_k),
}

# megablox's row tile
ROW_TILE = 128
# mean rows a group from which a grouped product fetches a group ahead
GROUP_AHEAD_FROM = 32
# (token, expert) pairs from which a held share's pairs are compacted
COMPACT_FROM = 16384


def row_tiling(rows: int, groups: int) -> Tuple[int, bool]:
    """(row tile, whether the kernel fetches a group's matrix a GROUP
    ahead) for a grouped product of `rows` sorted rows over `groups`
    matrices, from the MEAN ROWS A GROUP alone: static shapes, so a
    program's choice is made when it is traced.

    Under a tile's worth of rows a group a product is a step a group:
    megablox's pipeline, which fetches a STEP ahead, has every read
    behind a product.  That is a decode step (16 rows an expert for
    lfm2, 3 for kanana), whose programs stay as they were, and kanana's
    smallest prefill (24).  From 32 rows a group on, groups take two
    steps and more, megablox pays each group's read on top of its
    products, and `ops/grouped_matmul` starts the next group's read at
    the current group's FIRST step; with the reads hidden a product is
    its tiles' MXU time, so the tile is 64 rows: fewer rows computed
    for nothing where a group ends inside a tile (PERF.md section 6,
    PR 35: the sweep this rule was read from)."""
    if rows // groups < GROUP_AHEAD_FROM:
        return ROW_TILE, False
    return 64, True


def tile_visits(group_sizes, row_tile: int):
    """Grid steps of a grouped product over rows packed group after
    group from row 0: one a (row tile, group) pair that share a row,
    each a whole tile's product.  Over the groups with rows it is the
    tiles a group's rows reach into: 1.1 in a decode step, 2.2 at 162
    rows a group and 128 a tile."""
    ends = jnp.cumsum(group_sizes)
    first = (ends - group_sizes) // row_tile
    last = -(-ends // row_tile)
    return jnp.sum(jnp.where(group_sizes > 0, last - first, 0)
                   ).astype(jnp.int32)


def slab_rows(pairs: int, held: int, experts: int) -> int:
    """Sorted rows a slab of a held share's compact form takes (0: the
    pair-wide form), from static shapes alone: of `pairs` (token,
    expert) pairs the `held` of the router's `experts` get `pairs *
    held / experts` on average, and a slab is TWICE that mean in whole
    row tiles: at 16,384 pairs and 16 of 256 held 2,048 rows for a mean
    of 1,024 whose standard deviation is 31, so one slab holds them all
    but for a router that has collapsed, and then a second follows.

    Under `COMPACT_FROM` pairs the pair-wide form stays.  One expert
    layer at MiMo-V2.5's widths (16 of 256 experts of 4096 x 2048 held)
    and at dots3's (32 of 256 of 5120 x 1536), device time by op: at
    8,192 pairs the pair-wide form's two gathers are 0.19-0.22 ms
    beside 1.1 / 2.0 ms of grouped products and the forms tie (1.84
    against 1.61 ms a layer, 2.80 against 2.79); at 16,384 pairs XLA's
    gather of `[16384, D]` rows takes 2.0-2.5 ms and a layer is 4.15
    ms pair-wide, 2.26 compact.  A decode chunk's 1,024 pairs and every
    program under 2,048 tokens at top-8 stay what they were (PERF.md
    section 6, PR 52: the sweep)."""
    if pairs < COMPACT_FROM:
        return 0
    tiles = -(-min(2 * pairs * held // experts, pairs) // ROW_TILE)
    return max(tiles, 1) * ROW_TILE


def grouped_matmul(xs, w, group_sizes, *, kernel: bool = False,
                   interpret: bool = False, stack_index=None):
    """Rows of `xs` [M, K], sorted by group, each times its group's
    matrix of `w` [G, K, N]; `group_sizes` [G] sums to M or less (rows
    past the last group belong to none and are never read).  A group
    with no row is never read.  `kernel`: a Pallas grouped product
    (TPU), megablox's or `ops/grouped_matmul`'s by `row_tiling`;
    otherwise `lax.ragged_dot`, which every backend lowers.

    `w` may be a whole STACK of layers `[L, G, K, N]` with
    `stack_index` (traced) naming the layer.  That is how a layer scan
    hands the kernel its weights: a Pallas call cannot take a
    `dynamic_slice` of a stack as a fused operand, so slicing the
    layer out first is a copy of its experts every step (three 403 MB
    copies a layer at kanana's widths: 20 of a 33 ms decode step;
    PERF.md section 6, PR 27).  Instead the kernel sees all `L * G`
    groups, of which only the layer's own have rows; its grid is as
    long as the tiles that have work, so the other layers cost nothing."""
    if w.ndim == 4 and not kernel:
        w = lax.dynamic_index_in_dim(w, stack_index, 0, keepdims=False)
    if not kernel:
        return lax.ragged_dot(xs, w, group_sizes)
    M, K = xs.shape
    N = w.shape[-1]
    tm, group_ahead = row_tiling(M, group_sizes.shape[0])
    if w.ndim == 4:
        L, G = w.shape[:2]
        w = w.reshape((L * G,) + w.shape[2:])
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((L * G,), group_sizes.dtype), group_sizes,
            (stack_index * G,))
    pad = -M % tm
    if pad:  # the kernels walk whole row tiles; the tail belongs to no group
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    if group_ahead:
        from ray_tpu.ops.grouped_matmul import gmm

        out = gmm(xs, w, group_sizes, row_tile=tm, interpret=interpret)
    else:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        # whole K and N in one tile where they fit: 0.57 ms a product at
        # (128, 2048, 768) against 0.65-0.67 at tk 1024 / 512 (PERF.md,
        # PR 27)
        out = gmm(xs, w, group_sizes, preferred_element_type=xs.dtype,
                  tiling=(tm, min(K, 2048), min(N, 2048)),
                  interpret=interpret)
    return out[:M] if pad else out


def dropless_moe(h, layer: Dict, *, top_k: int, scale: float,
                 route_eps: float, dtype,
                 kernel: bool = False, interpret: bool = False,
                 stack_index=None, row_mask=None, held=None,
                 route="sigmoid"):
    """Routed experts for inference, nothing dropped: h [N, D] ->
    (y [N, D], stats).  `layer`: `router` [D, E] float32, `router_bias`
    [E], `e_gate` / `e_up` [E, D, I], `e_down` [E, I, D] — or the three
    expert leaves as whole stacks `[L, E, ...]` with `stack_index`
    naming the layer (see `grouped_matmul`).  A layer with NO `e_gate`
    holds two-matrix experts, `W_down relu(W_up x)^2` (`nemotron_h`'s
    `relu2`): two grouped products a pair where SwiGLU has three.
    `route`: a name of
    `ROUTES` ("sigmoid": `sigmoid_topk_route`, what every caller had;
    "softmax": `softmax_topk_route`, which reads no `router_bias`) or a
    callable of their signature (a model whose experts work in another
    width than its router reads routes before the call and hands the
    picks in: `h` is then what the EXPERTS take).

    The N * k (token, expert) pairs are sorted by expert, so each
    expert's rows are contiguous and the three SwiGLU products are
    grouped products over them; the results go back to token order by
    the inverse permutation (a gather, not a scatter-add) and are
    summed with the router's weights in float32.  There is no capacity:
    if one expert gets every token its group is the whole array.  A
    padding row routes like any other row and changes no other row's
    result.  `stats`, int32 scalars: `experts_touched` (groups with at
    least one row), `load_max` (rows of the largest group) and
    `tile_visits` (`tile_visits` of ONE of the three products at the
    row tile `row_tiling` gives; `ragged_dot` has no tiles and counts
    megablox's), `passes` (the slabs a held share's compact form
    walked, below; 0 on this pair-wide form) and `held_pairs` (the pairs
    that reached an expert here: the rows the grouped products took).

    `row_mask` [N] bool (the serve engine's live rows of a decode step;
    prefill passes none): a row it leaves out is routed to NO expert.
    Its pairs sort behind the last group and count in no group size, so
    the grouped products never read them, `stats` count the other rows
    only, and its row of `y` is zeros.

    `held` = `(offset, count)`: this chip's SHARE of an expert layer
    that several chips hold between them.  The router keeps its
    published width and the top-k is taken over all of its experts; the
    expert leaves hold experts `offset .. offset + count` only (`[count,
    ...]`).  A pair whose expert is not held goes to NO expert exactly
    as a masked row's pairs do (behind the last group, in no group
    size) and adds nothing to `y`, which is then this chip's PARTIAL sum
    (the other chips' parts are theirs to add: nothing here stands in
    for them); `stats` count the held experts.  From `COMPACT_FROM`
    pairs on (`slab_rows`, static shapes alone) nothing pair-wide is
    built for a share: the held pairs, the first `sum(sizes)` sorted
    rows, are gathered, multiplied and added back a slab at a time
    (`_held_slabs`), the same pairs and the same float32 sum in another
    order; `row_tiling` is asked with the slab's rows, and
    `tile_visits` is summed over the slabs."""
    N, D = h.shape
    E = layer["router"].shape[-1]
    rows = 0 if held is None else slab_rows(N * top_k, held[1], E)
    with jax.named_scope("moe_router"):
        w, idx = (route if callable(route) else ROUTES[route])(
            h, layer, top_k, scale, route_eps)
        if held is not None:  # the held experts renumbered from 0
            lo, E = held
            idx = jnp.where((idx >= lo) & (idx < lo + E), idx - lo, E)
        if row_mask is not None:  # expert E: behind every group, in none
            idx = jnp.where(row_mask[:, None], idx, E)
        flat = idx.reshape(-1)                      # [N * k], pair -> expert
        order = jnp.argsort(flat, stable=True)      # sorted row -> pair
        if not rows:
            inverse = jnp.argsort(order)            # pair -> sorted row
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")

    def swiglu(xs, sizes):
        mm = lambda a, b: grouped_matmul(  # noqa: E731
            a, b.astype(dtype), sizes, kernel=kernel, interpret=interpret,
            stack_index=stack_index)
        if "e_gate" not in layer:
            return mm(jnp.square(jax.nn.relu(mm(xs, layer["e_up"]))),
                      layer["e_down"])
        act = jax.nn.silu(mm(xs, layer["e_gate"])) * mm(xs, layer["e_up"])
        return mm(act, layer["e_down"])

    tile = row_tiling(rows or N * top_k, E)[0] if kernel else ROW_TILE
    visits, passes = None, jnp.int32(0)
    with jax.named_scope("moe_routed"):
        if rows:
            y, visits, passes = _held_slabs(
                h.astype(dtype), w, order, sizes, swiglu, top_k=top_k,
                rows=rows, tile=tile)
            y = y.astype(dtype)
        else:
            xs = h.astype(dtype)[order // top_k]    # [N * k, D]
            ys = swiglu(xs, sizes)                  # [N * k, D]
            y = ys[inverse].reshape(N, top_k, D).astype(jnp.float32)
            if held is not None:
                # a pair that went to no expert reads a row past the
                # last group: whatever the product left there
                y = jnp.where((idx < E)[..., None], y, 0.0)
            y = jnp.sum(y * w[..., None], axis=1).astype(dtype)
            if row_mask is not None:
                # rows of `ys` past the last group are whatever the
                # product left there, a NaN's bits too
                y = jnp.where(row_mask[:, None], y, jnp.zeros_like(y))
    stats = {"experts_touched": jnp.sum(sizes > 0).astype(jnp.int32),
             "load_max": jnp.max(sizes),
             "tile_visits": (tile_visits(sizes, tile) if visits is None
                             else visits),
             "passes": passes,
             "held_pairs": jnp.sum(sizes).astype(jnp.int32)}
    return y, stats


def _held_slabs(h, w, order, sizes, swiglu, *, top_k: int, rows: int,
                tile: int):
    """`dropless_moe`'s compact form for a held share: the pairs with a
    group are the first `sum(sizes)` sorted rows, and only they are
    gathered, multiplied and added back, a SLAB of `rows` sorted rows at
    a time (`slab_rows`: static).  A slab gathers its rows of `h`, runs
    `swiglu` (the three grouped products) on `[rows, D]` with each
    group's size clipped to the slab, and scatter-adds its rows, times
    the router's weights in float32, into `y [N, D]` float32 by token.
    The slabs are walked while one begins under `sum(sizes)`: one in
    the normal case, more where the router sends this chip more than
    twice its mean, so nothing is dropped whatever the routing.  A token
    none of whose pairs is held (a masked row among them) keeps its
    zeros.  -> (y float32, the slabs' `tile_visits` summed, slabs
    walked)."""
    N, D = h.shape
    total, ends = jnp.sum(sizes), jnp.cumsum(sizes)
    starts = ends - sizes
    # a slab is sliced whole: the last may reach past the pairs
    order = jnp.pad(order, (0, -order.shape[0] % rows))
    w = w.reshape(-1)

    def slab(carry):
        lo, y, visits = carry
        pairs = lax.dynamic_slice(order, (lo,), (rows,))
        tok = pairs // top_k
        here = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
        ys = swiglu(h[tok], here).astype(jnp.float32) * w[pairs][:, None]
        # rows past the last group are whatever the product left there
        paired = lo + jnp.arange(rows, dtype=jnp.int32) < total
        y = y.at[tok].add(jnp.where(paired[:, None], ys, 0.0))
        return lo + rows, y, visits + tile_visits(here, tile)

    lo, y, visits = lax.while_loop(
        lambda carry: carry[0] < total, slab,
        (jnp.int32(0), jnp.zeros((N, D), jnp.float32), jnp.int32(0)))
    return y, visits, lo // rows


# ----------------------------------------------------------------------
# the same held share, trained
# ----------------------------------------------------------------------
# rows a grouped product of the trained share takes a grid step: its
# groups hold a thousand rows and more, so a tile is as tall as the
# accumulator allows and a group's end inside a tile costs little
TRAIN_ROW_TILE = 256


def train_slab_rows(pairs: int, held: int, experts: int,
                    tile: int = TRAIN_ROW_TILE) -> int:
    """Sorted rows a slab of the trained share takes: twice the mean
    the held experts get of `pairs` (token, expert) pairs, in whole row
    tiles, and never more than all the pairs.  Static shapes alone, as
    `slab_rows`; there is no pair-wide form under it."""
    up = lambda x: -(-x // tile) * tile  # noqa: E731
    return min(up(max(2 * pairs * held // experts, 1)), up(pairs))


def dropless_moe_train(h, layer: Dict, bias, *, top_k: int, scale: float,
                       route_eps: float, dtype, held: Tuple[int, int],
                       kernel: bool = False, interpret: bool = False):
    """`dropless_moe(held=)` that can be differentiated: h [N, D] ->
    (y [N, D] float32, stats).  `layer`: `router` [D, E_all] float32,
    `e_gate` / `e_up` [count, D, I], `e_down` [count, I, D] in the
    parameters' dtype (float32: `grouped_product` rounds them to `dtype`
    inside and returns their gradients unrounded); `bias` [E_all] is the
    router's balancing bias, an input with NO gradient (it only moves
    the pick).  `held` = `(offset, count)`: the experts this chip holds.

    The pick is `sigmoid_topk_route`'s: the `top_k` of `sigmoid(h W) +
    bias` over ALL `E_all` experts, the weights the picked scores
    WITHOUT the bias over their sum, times `scale`, all in float32.  A
    pair whose expert is not held adds nothing here: `y` is this chip's
    partial sum and nothing stands in for the absent chips.

    NO pair of a held expert is dropped whatever the load, with static
    shapes: the held pairs are the first `sum(sizes)` rows of the pairs
    sorted by expert, and they are walked in slabs of `rows =
    train_slab_rows(...)` sorted rows (`_held_slabs_train`): as many
    slabs as have work, one where the load is up to twice its mean, all
    `ceil(N * top_k / rows)` where every pair is held.

    `stats`: `counts` [E_all] int32 (the pairs EVERY expert of the
    router was picked for: the bias's rule and the load counters read
    it), `held_pairs` (pairs of held experts: the rows the grouped
    products took), `slabs` (slabs that had work)."""
    N, D = h.shape
    E_all = layer["router"].shape[-1]
    lo, E = held
    pairs = N * top_k
    tile = TRAIN_ROW_TILE if kernel else ROW_TILE
    rows = train_slab_rows(pairs, E, E_all, tile)
    with jax.named_scope("moe_router"):
        w, idx = sigmoid_topk_route(
            h, layer["router"], lax.stop_gradient(bias), top_k, scale,
            route_eps)
        # a compare and a column sum: a scatter of N * top_k ones is a
        # serial walk on the chip
        counts = jnp.sum(
            (idx.reshape(-1, 1) == jnp.arange(E_all, dtype=jnp.int32)
             ).astype(jnp.int32), axis=0)
        local = jnp.where((idx >= lo) & (idx < lo + E), idx - lo, E)
        order = jnp.argsort(local.reshape(-1), stable=True)  # row -> pair
        order = jnp.pad(order, (0, -pairs % rows)).astype(jnp.int32)
        sizes = lax.dynamic_slice(counts, (lo,), (E,))
    with jax.named_scope("moe_routed"):
        y = _held_slabs_train(
            (top_k, rows, tile, dtype, kernel, interpret),
            h.astype(dtype), w.reshape(-1), layer["e_gate"], layer["e_up"],
            layer["e_down"], order, sizes)
    total = jnp.sum(sizes)
    stats = {"counts": counts, "held_pairs": total,
             "slabs": -(-total // rows)}
    return y, stats


def _train_slab(static, hx, wf, e_gate, e_up, e_down, order, sizes, at):
    """What the sorted rows `[at, at + rows)` add to `y`: `[N, D]`
    float32, zeros but for their tokens' rows."""
    from ray_tpu.ops.grouped_matmul import grouped_product

    top_k, rows, tile, dtype, kernel, interpret = static
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    here = jnp.clip(ends, at, at + rows) - jnp.clip(starts, at, at + rows)

    def product(xs, mat):
        if kernel:
            return grouped_product(xs, mat, here, tile, interpret)
        ys = lax.ragged_dot(xs, mat.astype(dtype), here)
        # `ragged_dot` leaves zeros past the last group on every
        # backend; the where keeps that a property of THIS function
        row = lax.broadcasted_iota(jnp.int32, ys.shape, 0)
        return jnp.where(row < jnp.sum(here), ys, jnp.zeros_like(ys))

    pair = lax.dynamic_slice(order, (at,), (rows,))
    tok = pair // top_k
    xs = hx[tok]
    act = jax.nn.silu(product(xs, e_gate)) * product(xs, e_up)
    ys = product(act, e_down).astype(jnp.float32)
    paired = (at + jnp.arange(rows, dtype=jnp.int32) < ends[-1])[:, None]
    return jnp.zeros((hx.shape[0], hx.shape[1]), jnp.float32).at[tok].add(
        jnp.where(paired, ys * wf[pair][:, None], 0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_slabs_train(static, hx, wf, e_gate, e_up, e_down, order, sizes):
    """The held pairs' sum into `y [N, D]` float32, a slab of `rows`
    sorted rows at a time, forward and backward a `lax.while_loop` over
    the slabs THAT HAVE WORK (`_held_slabs`' walk; a `while_loop` has no
    reverse of its own, so this function brings it).  The first slab
    always runs, and its own `jax.vjp` is what the backward pass starts
    from: in the normal case, one slab, the layer's experts are computed
    once a pass and nothing is recomputed here.  A later slab keeps
    nothing: the backward pass walks the same slabs again and takes each
    one's `jax.vjp` there, its gradients added into the first slab's
    (one set of buffers, however many slabs).  What the bound costs: a
    gather and a scatter-add of `rows` rows a slab whatever the load
    (the padding past the last pair is never multiplied: the grouped
    products walk tiles with rows only), and one more forward of every
    slab past the first."""
    return _slabs_fwd(static, hx, wf, e_gate, e_up, e_down, order, sizes)[0]


def _slabs_fwd(static, hx, wf, e_gate, e_up, e_down, order, sizes):
    rows, total = static[1], jnp.sum(sizes)
    diff = (hx, wf, e_gate, e_up, e_down)
    y, first = jax.vjp(lambda *a: _train_slab(
        static, *a, order, sizes, jnp.int32(0)), *diff)
    _, y = lax.while_loop(
        lambda c: c[0] < total,
        lambda c: (c[0] + rows, c[1] + _train_slab(
            static, *diff, order, sizes, c[0])),
        (jnp.int32(rows), y))
    return y, (first, diff, order, sizes)


def _slabs_bwd(static, res, dy):
    first, diff, order, sizes = res
    rows, total = static[1], jnp.sum(sizes)

    def later(c):
        at, grads = c
        _, back = jax.vjp(lambda *a: _train_slab(
            static, *a, order, sizes, at), *diff)
        return at + rows, jax.tree.map(jnp.add, grads, back(dy))

    _, grads = lax.while_loop(lambda c: c[0] < total, later,
                              (jnp.int32(rows), first(dy)))
    return (*grads, None, None)


_held_slabs_train.defvjp(_slabs_fwd, _slabs_bwd)
