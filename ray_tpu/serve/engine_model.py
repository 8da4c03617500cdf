"""The seam between the serve engine and a model.

`LlamaEngine` (`serve/llm_engine.py`) is a scheduler over a paged cache:
admission, shedding, block tables, the radix prefix cache, the tick and
its rings.  What it computes WITH is behind this seam.  An engine model
tells the engine

- its CACHE SPEC: the pool leaves one cached token needs, each as the
  shape after `[layers, num_blocks, block_size]` and a dtype
  (`cache_leaves`), and the bytes one token costs (`cache_bytes_per_token`);
- four program bodies, each keyed by the static shape the engine buckets
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
    routes' masked select), the latent model routes it to no expert,
    and the token it yields is nobody's;
  - `prefill(bucket)`: `(params, prompt [1, bucket]) -> (logits
    [bucket, vocab], *kv)`, `kv` the rows to cache, `[L, 1, bucket, ...]`;
  - `suffix_prefill(s_bucket, p_blocks)`: `(params, *cache, suffix,
    blk_ids, prefix_len) -> (logits, *kv)`: prefill behind a cached
    prefix, read from the pool through `blk_ids`;
  - `kv_write(t_in, nb)`: `(*cache, *kv, blk_ids, slot, pos0, tok0,
    pos, tok, stop0, stop) -> (*cache, pos, tok, stop)`: the prefilled
    rows into their blocks, and the admitted slot's `pos`, `tok` and
    `stop` (`T + n_new - 1`: the engine is greedy with a fixed budget,
    so the host never has to tell the device that a row ended).
    Without `stop0, stop` it returns `(*cache, pos, tok)`.

Two implementers: `LlamaEngineModel` (per-head K and V pools, optional
int8 pools with a scale sidecar: the program bodies `LlamaEngine` has
always run, moved here unchanged) and `LatentMoeEngineModel`
(`models/deepseek_v3.py`: ONE latent pool, absorbed decode attention,
dropless experts).  `engine_model_for` picks by the config's type: a
user passes a model's config and the model picks its route.
"""

from __future__ import annotations

from typing import Dict, List

from ray_tpu.serve.kv_cache import CacheLeaf


def _set_slot(slot, pos0, tok0, pos, tok, stop0, stop):
    """The tail of both `kv_write` bodies: an admitted slot's rows of
    the device state.  A caller that only wants blocks written passes
    no stop and gets `(pos, tok)` back."""
    out = (pos.at[slot].set(pos0), tok.at[slot].set(tok0))
    if stop is not None:
        out += (stop.at[slot].set(stop0),)
    return out


class LlamaEngineModel:
    """Per-head K / V pools `[L, num_blocks, block_size, KV, hd]`; with
    `kv_dtype="int8"` an int8 payload plus a float32 scale sidecar
    `[L, num_blocks, block_size, KV]` per pool."""

    aux_rows = 0

    def __init__(self, cfg, *, slots: int, max_len: int, chunk: int,
                 block_size: int, decode_kernel: str, kv_int8: bool,
                 kernel_interpret: bool):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import llama

        self._jax, self._jnp, self._llama = jax, jnp, llama
        self.cfg = cfg
        self.slots, self.max_len, self.chunk = slots, max_len, chunk
        self.block_size = block_size
        self._decode_kernel = decode_kernel
        self._kv_int8 = kv_int8
        self._kernel_interpret = kernel_interpret
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        payload = jnp.int8 if kv_int8 else cfg.dtype
        self.n_layers = cfg.n_layers
        self.cache_leaves: List[CacheLeaf] = [
            CacheLeaf("k", (KV, hd), payload), CacheLeaf("v", (KV, hd), payload)]
        if kv_int8:
            # one f32 scale per (layer, row, kv-head), written by the
            # same paths that write KV rows
            self.cache_leaves += [
                CacheLeaf("k_scale", (KV,), jnp.float32, sidecar=True),
                CacheLeaf("v_scale", (KV,), jnp.float32, sidecar=True)]
        self.n_kv = 2  # prefill hands back K and V

    def tick_fields(self, aux) -> Dict[str, object]:
        return {}

    # -- compiled-program bodies (moved from LlamaEngine, unchanged) ----
    def decode_chunk(self, W: int):
        jax, jnp, llama = self._jax, self._jnp, self._llama
        cfg, bs, chunk = self.cfg, self.block_size, self.chunk
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        S = self.slots

        if self._decode_kernel == "pallas":
            interp = self._kernel_interpret
            if self._kv_int8:
                def _fn(params, k_pool, v_pool, k_scale, v_scale,
                        tables, tok, pos, stop):
                    def body(carry, _):
                        tok, kp, vp, ks, vs, pos = carry
                        live = pos < stop
                        logits, kp, vp, ks, vs = llama.decode_step_paged(
                            cfg, params, tok, kp, vp, tables, pos,
                            kv_scales=(ks, vs), live=live, interpret=interp,
                        )
                        nt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        pos2 = jnp.where(live, pos + 1, pos)
                        return (nt, kp, vp, ks, vs, pos2), nt

                    tok_in = tok
                    (tok, k_pool, v_pool, k_scale, v_scale, pos), toks = \
                        jax.lax.scan(
                            body,
                            (tok, k_pool, v_pool, k_scale, v_scale, pos),
                            None, length=chunk,
                        )
                    return (k_pool, v_pool, k_scale, v_scale, tok, pos,
                            jnp.concatenate([tok_in[None], toks], axis=0))

            else:
                def _fn(params, k_pool, v_pool, tables, tok, pos, stop):
                    def body(carry, _):
                        tok, kp, vp, pos = carry
                        # a row owes a token while it is short of its
                        # stop; a dead row stays where it is
                        live = pos < stop
                        logits, kp, vp = llama.decode_step_paged(
                            cfg, params, tok, kp, vp, tables, pos,
                            live=live, interpret=interp,
                        )
                        nt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        pos2 = jnp.where(live, pos + 1, pos)
                        return (nt, kp, vp, pos2), nt

                    tok_in = tok  # pre-chunk tokens (see gather route)
                    (tok, k_pool, v_pool, pos), toks = jax.lax.scan(
                        body, (tok, k_pool, v_pool, pos), None,
                        length=chunk,
                    )
                    return k_pool, v_pool, tok, pos, jnp.concatenate(
                        [tok_in[None], toks], axis=0
                    )

        elif self._kv_int8:
            from ray_tpu.ops import paged_attention as _pa

            def _fn(params, k_pool, v_pool, k_scale, v_scale, tables,
                    tok, pos, stop):
                # gather payload + scales, dequant to the compute dtype
                kq = jnp.take(k_pool, tables, axis=1).reshape(
                    L, S, W * bs, KV, hd
                )
                vq = jnp.take(v_pool, tables, axis=1).reshape(
                    L, S, W * bs, KV, hd
                )
                ks = jnp.take(k_scale, tables, axis=1).reshape(
                    L, S, W * bs, KV
                )
                vs = jnp.take(v_scale, tables, axis=1).reshape(
                    L, S, W * bs, KV
                )
                k = _pa.dequantize_int8(kq, ks, cfg.dtype)
                v = _pa.dequantize_int8(vq, vs, cfg.dtype)
                pos0 = pos

                def body(carry, _):
                    tok, kv, pos = carry[0], (carry[1], carry[2]), carry[3]
                    live = pos < stop
                    logits, (k2, v2) = llama.decode_step_vec(
                        cfg, params, tok, kv, pos, live
                    )
                    nt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    pos2 = jnp.where(live, pos + 1, pos)
                    return (nt, k2, v2, pos2), nt

                tok_in = tok
                (tok, k, v, pos), toks = jax.lax.scan(
                    body, (tok, k, v, pos), None, length=chunk
                )
                # requantize ONLY the rows this chunk wrote (a row's
                # live steps: pos0 up to where it stands now); untouched
                # rows keep their stored payload+scale bit-exactly, so
                # repeated gather/scatter cycles cannot drift the cache
                # (a full-view requant would re-round every row through
                # the compute dtype each chunk)
                idx = jnp.arange(W * bs)[None, :]
                touched = ((idx >= pos0[:, None])
                           & (idx < pos[:, None]))  # [S, M]
                kq2, ks2 = _pa.quantize_int8(k)
                vq2, vs2 = _pa.quantize_int8(v)
                t_p = touched[None, :, :, None, None]
                t_s = touched[None, :, :, None]
                kq2 = jnp.where(t_p, kq2, kq)
                vq2 = jnp.where(t_p, vq2, vq)
                ks2 = jnp.where(t_s, ks2, ks)
                vs2 = jnp.where(t_s, vs2, vs)
                k_pool = k_pool.at[:, tables].set(
                    kq2.reshape(L, S, W, bs, KV, hd)
                )
                v_pool = v_pool.at[:, tables].set(
                    vq2.reshape(L, S, W, bs, KV, hd)
                )
                k_scale = k_scale.at[:, tables].set(
                    ks2.reshape(L, S, W, bs, KV)
                )
                v_scale = v_scale.at[:, tables].set(
                    vs2.reshape(L, S, W, bs, KV)
                )
                return (k_pool, v_pool, k_scale, v_scale, tok, pos,
                        jnp.concatenate([tok_in[None], toks], axis=0))

        else:
            def _fn(params, k_pool, v_pool, tables, tok, pos, stop):
                # tables [slots, W] -> dense [L, slots, W*bs, KV, hd]
                k = jnp.take(k_pool, tables, axis=1).reshape(
                    L, S, W * bs, KV, hd
                )
                v = jnp.take(v_pool, tables, axis=1).reshape(
                    L, S, W * bs, KV, hd
                )

                def body(carry, _):
                    tok, kv, pos = carry[0], (carry[1], carry[2]), carry[3]
                    live = pos < stop
                    logits, (k2, v2) = llama.decode_step_vec(
                        cfg, params, tok, kv, pos, live
                    )
                    nt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    pos2 = jnp.where(live, pos + 1, pos)
                    return (nt, k2, v2, pos2), nt

                tok_in = tok  # pre-chunk tokens: a freshly admitted
                # slot's FIRST token (from prefill) — emitting it here
                # means admission never needs its own device->host read
                # (one full round trip PER REQUEST)
                (tok, k, v, pos), toks = jax.lax.scan(
                    body, (tok, k, v, pos), None, length=chunk
                )
                # scatter the (updated) blocks back into the pool.
                # Shared prefix blocks scatter identical, unmodified
                # values from every sharer (a dead row wrote nothing
                # into its view); padding rows target the scratch
                # block — both make duplicate indices benign.
                kb = k.reshape(L, S, W, bs, KV, hd)
                vb = v.reshape(L, S, W, bs, KV, hd)
                k_pool = k_pool.at[:, tables].set(kb)
                v_pool = v_pool.at[:, tables].set(vb)
                # [1 + chunk, slots]: row 0 = pre-chunk tokens
                return k_pool, v_pool, tok, pos, jnp.concatenate(
                    [tok_in[None], toks], axis=0
                )
        return _fn

    def prefill(self, bucket: int):
        llama = self._llama

        def _pf(params, prompt):  # prompt [1, bucket]
            # full-sequence logits (not llama.prefill's last-pos
            # form): the prompt is right-padded to the bucket, so
            # the real continuation logit lives at position T-1.
            # Garbage KV rows written for pad positions stay masked
            # (pos starts at T) and are overwritten as decoding
            # advances through them.
            logits, (ks, vs) = llama.forward(
                self.cfg, params, prompt, return_kv=True
            )
            return logits[0], ks, vs  # ks/vs [L, 1, bucket, KV, hd]

        return _pf

    def suffix_prefill(self, s_bucket: int, p_blocks: int):
        jax, jnp, llama = self._jax, self._jnp, self._llama
        cfg, bs = self.cfg, self.block_size
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim

        if self._kv_int8:
            from ray_tpu.ops import paged_attention as _pa

            def _pf(params, k_pool, v_pool, k_scale, v_scale,
                    suffix, blk_ids, prefix_len):
                pk = _pa.dequantize_int8(
                    jnp.take(k_pool, blk_ids, axis=1),
                    jnp.take(k_scale, blk_ids, axis=1), cfg.dtype,
                ).reshape(L, 1, p_blocks * bs, KV, hd)
                pv = _pa.dequantize_int8(
                    jnp.take(v_pool, blk_ids, axis=1),
                    jnp.take(v_scale, blk_ids, axis=1), cfg.dtype,
                ).reshape(L, 1, p_blocks * bs, KV, hd)
                logits, (ks, vs) = llama.forward_with_prefix(
                    cfg, params, suffix, (pk, pv), prefix_len
                )
                return logits[0], ks, vs
        else:
            def _pf(params, k_pool, v_pool, suffix, blk_ids,
                    prefix_len):
                pk = jnp.take(k_pool, blk_ids, axis=1).reshape(
                    L, 1, p_blocks * bs, KV, hd
                )
                pv = jnp.take(v_pool, blk_ids, axis=1).reshape(
                    L, 1, p_blocks * bs, KV, hd
                )
                logits, (ks, vs) = llama.forward_with_prefix(
                    cfg, params, suffix, (pk, pv), prefix_len
                )
                return logits[0], ks, vs
        return _pf

    def kv_write(self, t_in: int, nb: int):
        jax, jnp = self._jax, self._jnp
        bs = self.block_size
        L, KV, hd = (self.cfg.n_layers, self.cfg.n_kv_heads,
                     self.cfg.head_dim)
        target = nb * bs

        def _clip(k1, v1):
            # k1/v1 [L, 1, t_in, KV, hd] -> exactly nb blocks
            if t_in < target:
                pad = [(0, 0), (0, 0), (0, target - t_in), (0, 0),
                       (0, 0)]
                return jnp.pad(k1, pad), jnp.pad(v1, pad)
            if t_in > target:
                return k1[:, :, :target], v1[:, :, :target]
            return k1, v1

        if self._kv_int8:
            from ray_tpu.ops import paged_attention as _pa

            def _fn(k_pool, v_pool, k_scale, v_scale, k1, v1,
                    blk_ids, slot, pos0, tok0, pos, tok,
                    stop0=None, stop=None):
                k1, v1 = _clip(k1, v1)
                kq, ksc = _pa.quantize_int8(k1)  # [L,1,target,KV]
                vq, vsc = _pa.quantize_int8(v1)
                k_pool = k_pool.at[:, blk_ids].set(
                    kq.reshape(L, nb, bs, KV, hd)
                )
                v_pool = v_pool.at[:, blk_ids].set(
                    vq.reshape(L, nb, bs, KV, hd)
                )
                k_scale = k_scale.at[:, blk_ids].set(
                    ksc.reshape(L, nb, bs, KV)
                )
                v_scale = v_scale.at[:, blk_ids].set(
                    vsc.reshape(L, nb, bs, KV)
                )
                return (k_pool, v_pool, k_scale, v_scale) + _set_slot(
                    slot, pos0, tok0, pos, tok, stop0, stop)

        else:
            def _fn(k_pool, v_pool, k1, v1, blk_ids, slot, pos0,
                    tok0, pos, tok, stop0=None, stop=None):
                k1, v1 = _clip(k1, v1)
                kb = k1.astype(k_pool.dtype).reshape(
                    L, nb, bs, KV, hd
                )
                vb = v1.astype(v_pool.dtype).reshape(
                    L, nb, bs, KV, hd
                )
                k_pool = k_pool.at[:, blk_ids].set(kb)
                v_pool = v_pool.at[:, blk_ids].set(vb)
                return (k_pool, v_pool) + _set_slot(
                    slot, pos0, tok0, pos, tok, stop0, stop)
        return _fn


class LatentMoeEngineModel:
    """`models/deepseek_v3.py` behind the seam: ONE latent pool
    `[L, num_blocks, block_size, Dp]` — a token and layer cache the
    normalised compressed KV beside the rotated shared key, 576 values
    (`Dp` is 576 rounded up to whole lanes, 640: see
    `ops/paged_attention.MLA_LANES`) and nothing per head.  Prefill
    (and the suffix prefill behind a cached prefix) expands the latents
    through `W_kvb`; decode is the absorbed form on the pool as it
    lies.  The decode program hands back two counters of the expert
    layers with its tokens (`aux_rows`)."""

    aux_rows = 2  # [experts_touched summed over the chunk | load_max]

    def __init__(self, cfg, *, slots: int, max_len: int, chunk: int,
                 block_size: int, decode_kernel: str, kv_int8: bool,
                 kernel_interpret: bool):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import deepseek_v3
        from ray_tpu.ops.paged_attention import mla_pool_width

        if kv_int8:
            raise ValueError(
                "kv_dtype='int8' quantizes per-head K and V rows; the "
                "latent pool has no heads to scale by")
        self._jax, self._jnp, self._m = jax, jnp, deepseek_v3
        self.cfg = cfg
        self.slots, self.max_len, self.chunk = slots, max_len, chunk
        self.block_size = block_size
        # "pallas": latent kernels + megablox grouped products (TPU);
        # "gather": dense view + `lax.ragged_dot` (anywhere)
        self._kernel = decode_kernel == "pallas"
        self._interpret = kernel_interpret
        self.n_layers = cfg.n_layers
        self.width = mla_pool_width(cfg.latent_dim)
        self.cache_leaves: List[CacheLeaf] = [
            CacheLeaf("latent", (self.width,), cfg.dtype,
                      used=cfg.latent_dim)]
        self.n_kv = 1
        self._pairs = cfg.n_moe_layers * cfg.n_routed_experts

    def tick_fields(self, aux) -> Dict[str, object]:
        """`aux` [2, slots] from a harvested chunk: the decode steps'
        distinct (layer, expert) pairs, a step's mean over the chunk
        (of `n_moe_layers * n_routed_experts`), and the most rows any
        one expert got in any step and layer."""
        return {"experts_touched": float(aux[0, 0]) / self.chunk,
                "experts_total": self._pairs,
                "expert_load_max": int(aux[1, 0])}

    def _kw(self):
        # interpret mode walks the grouped product tile by tile in
        # Python: the CPU kernel tests take `lax.ragged_dot` instead
        return dict(kernel=self._kernel and not self._interpret,
                    interpret=self._interpret)

    def decode_chunk(self, W: int):
        jax, jnp, m = self._jax, self._jnp, self._m
        cfg, bs, chunk, S = self.cfg, self.block_size, self.chunk, self.slots
        L, Dp = cfg.n_layers, self.width
        paged, kw = self._kernel, self._kw()

        def _fn(params, pool, tables, tok, pos, stop):
            # kernel route: the pool in place through the tables; else
            # the dense view [L, slots, W * bs, Dp], scattered back
            cache = pool if paged else jnp.take(
                pool, tables, axis=1).reshape(L, S, W * bs, Dp)

            def body(carry, _):
                tok, cache, pos = carry
                live = pos < stop
                logits, cache, st = m.decode_step(
                    cfg, params, tok, cache, pos,
                    tables=tables if paged else None, live=live, **kw)
                nt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                pos2 = jnp.where(live, pos + 1, pos)
                return (nt, cache, pos2), (
                    nt, st["experts_touched"], st["load_max"])

            tok_in = tok  # pre-chunk tokens: row 0 (see LlamaEngineModel)
            (tok, cache, pos), (toks, touched, load) = jax.lax.scan(
                body, (tok, cache, pos), None, length=chunk)
            if not paged:
                cache = pool.at[:, tables].set(
                    cache.reshape(L, S, W, bs, Dp))
            aux = jnp.stack([jnp.sum(touched), jnp.max(load)])
            return cache, tok, pos, jnp.concatenate(
                [tok_in[None], toks,
                 jnp.broadcast_to(aux[:, None], (2, S)).astype(jnp.int32)],
                axis=0)

        return _fn

    def prefill(self, bucket: int):
        def _pf(params, prompt):  # prompt [1, bucket], right-padded
            logits, lat = self._m.forward(self.cfg, params, prompt,
                                          return_kv=True, **self._kw())
            return logits[0], lat  # lat [L, 1, bucket, 576]

        return _pf

    def suffix_prefill(self, s_bucket: int, p_blocks: int):
        jnp = self._jnp
        L, bs = self.cfg.n_layers, self.block_size

        def _pf(params, pool, suffix, blk_ids, prefix_len):
            prefix = jnp.take(pool, blk_ids, axis=1).reshape(
                L, 1, p_blocks * bs, self.width)
            logits, lat = self._m.forward_with_prefix(
                self.cfg, params, suffix, prefix, prefix_len, **self._kw())
            return logits[0], lat

        return _pf

    def kv_write(self, t_in: int, nb: int):
        jnp = self._jnp
        L, bs = self.cfg.n_layers, self.block_size
        target = nb * bs

        def _fn(pool, lat, blk_ids, slot, pos0, tok0, pos, tok,
                stop0=None, stop=None):
            # lat [L, 1, t_in, 576] -> exactly nb blocks of Dp columns
            lat = lat[:, 0, :target]
            lat = jnp.pad(lat, ((0, 0), (0, target - lat.shape[1]),
                                (0, self.width - lat.shape[2])))
            pool = pool.at[:, blk_ids].set(
                lat.astype(pool.dtype).reshape(L, nb, bs, self.width))
            return (pool,) + _set_slot(slot, pos0, tok0, pos, tok, stop0,
                                       stop)

        return _fn


def engine_model_for(cfg, **kw):
    """The implementer for a model's config: the model picks its route."""
    from ray_tpu.models.deepseek_v3 import DeepseekV3Config

    if isinstance(cfg, DeepseekV3Config):
        return LatentMoeEngineModel(cfg, **kw)
    return LlamaEngineModel(cfg, **kw)
