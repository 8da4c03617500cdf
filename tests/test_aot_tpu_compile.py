"""The chip's compiler, without the chip: every Pallas kernel on the
train and serve paths is lowered by Mosaic for a DESCRIBED v5e device
at the widths `chip_smoke.py` runs (GPT-2 124M, llama1b4) — the
`on-chip-measurement` guide's third rehearsal, kept as tier-1 tests.

Interpret mode cannot see what this does: a block shape the TPU tiling
refuses, too much VMEM, an operand that cannot alias.  (The int8 append
kernel passed every interpret test and was refused here until its
per-row scale operands became `[B, 1, KV]` blocks.)  Nothing runs, so
there are no results or times to check — a compile that passes is not
a chip run.

The file sorts first on purpose: the suite has hit its time limit
before, and a test the clock never reaches guards nothing.  Skipped
only where the topology cannot be described (no libtpu).
"""

import dataclasses
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.ops import flash_attention  # noqa: E402
from ray_tpu.ops import paged_attention as pa  # noqa: E402

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def chip():
    """One device of a described `v5e:2x2` slice, as a sharding to hang
    on argument shapes.  The compile cache is off around the module: a
    TPU executable written here could not be read back without a chip,
    and the next run would warn instead of staying silent."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # libtpu lets ONE process load it (a lock file in /tmp) because a
    # chip has one owner.  Describing a topology owns no chip, and
    # several pytest processes may do it at once (xdist workers, a
    # second session): say so for the load, then take the word back so
    # no worker spawned later inherits it
    had = os.environ.get("ALLOW_MULTIPLE_LIBTPU_LOAD")
    os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # rtlint: disable=RT005 — the skip reason
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    finally:
        if had is None:
            del os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"]
        else:
            os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = had
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(chip, fn, *args, **jit_kw):
    """Lower `fn` for the described chip from argument SHAPES (there is
    no device to hold an array) and compile it; returns the HLO text."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        args,
    )
    return jax.jit(fn, **jit_kw).lower(*shapes).compile().as_text()


def _s(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def llama1b4() -> llama.LlamaConfig:
    from ray_tpu.examples.serve_llm import _model_config

    return _model_config("llama1b4")


def _bf16_params(cfg):
    """Parameter shapes as the serve path holds them (bf16)."""
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
    )
    return jax.tree.map(lambda p: _s(*p.shape), shapes)


# ----------------------------------------------------------------------
# train path: flash attention forward + backward
# ----------------------------------------------------------------------
def _flash_grad_hlo(chip, B, T, H, D, block):
    def loss(q, k, v):
        out = flash_attention(q, k, v, True, block, block)
        return jnp.sum(out.astype(jnp.float32))

    qkv = [_s(B, T, H, D)] * 3
    return _compile(chip, jax.grad(loss, argnums=(0, 1, 2)), *qkv)


@pytest.mark.parametrize("B,T,H,D,block", [
    # GPT-2 124M at the smoke's batch: single tile -> FUSED backward
    (16, 1024, 12, 64, 1024),
    # the benchmark's train cell (gpt2-medium): the same, 16 heads
    (16, 1024, 16, 64, 1024),
    # llama1b4 LoRA bench shape: single tile, D=128
    (8, 1024, 16, 128, 1024),
    # long sequences: multi-block forward + SPLIT dq / dkv backward
    (1, 4096, 12, 64, 1024),
    (1, 2048, 16, 128, 512),
], ids=["gpt2-fused", "gpt2m-cell-fused", "llama1b4-fused", "t4096-split",
        "t2048-d128-split"])
def test_flash_attention_fwd_bwd(chip, B, T, H, D, block):
    hlo = _flash_grad_hlo(chip, B, T, H, D, block)
    # forward + fused backward, or forward + dq + dkv
    assert hlo.count("tpu_custom_call") >= (2 if block == T else 3)


def _train_cell():
    """`gpt2m_train_stream`'s configuration and traffic files."""
    import json
    import pathlib

    bench = pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
    return (json.loads((bench / "configs" / "gpt2-medium.json").read_text()),
            json.loads((bench / "traffic" / "train_stream.json").read_text()))


def _flash_calls(hlo, cfg, mix):
    """The custom calls of `hlo` by the label the benchmark's train
    plane gives them (`kernel_predicates`, read here and not edited)."""
    from benchmarks.planes.train import kernel_predicates

    return {label: [ln.split(" = ")[0].strip() for ln in hlo.splitlines()
                    if pred(ln.strip())]
            for label, pred in kernel_predicates(cfg, mix).items()}


def test_flash_calls_are_what_the_benchmark_looks_for(chip):
    """`gpt2m_train_stream` finds the two kernels in a device trace by
    the custom calls' RESULT shapes (`benchmarks/planes/train.py::
    kernel_predicates`): a kernel change that returned anything else
    would turn `flash_fwd_roofline` and `flash_bwd_roofline` into null
    on the chip.  Fail here instead."""
    cfg, mix = _train_cell()
    m = cfg["model"]
    hlo = _flash_grad_hlo(chip, int(mix["batch"]), int(mix["seq"]),
                          m["n_head"], m["n_embd"] // m["n_head"], 1024)
    found = _flash_calls(hlo, cfg, mix)
    assert len(found["flash_fwd"]) == 1 and "flash_fwd" in found["flash_fwd"][0]
    assert len(found["flash_bwd"]) == 1 and "flash_bwd" in found["flash_bwd"][0]


def test_the_cells_train_step_holds_the_flash_forward_once(chip):
    """`gpt2m_train_stream`'s step (`remat=True`, `attention="flash"`,
    gpt2-medium's widths at the cell's batch; 2 layers, the scan's body
    is the same at 24) compiled for the chip: the backward scan's replay
    reads the forward kernel's kept results (`FLASH_RESIDUALS`), so the
    program holds ONE call the benchmark counts as `flash_fwd` and one
    it counts as `flash_bwd`.  A bare `jax.checkpoint` holds two and
    one, and `flash_fwd_calls_per_bwd` reads 2.0 on the chip."""
    import optax

    from ray_tpu.models import gpt2

    cfg, mix = _train_cell()
    m, tr = cfg["model"], cfg["trainer"]
    gcfg = gpt2.GPT2Config(
        vocab_size=m["vocab_size"], n_positions=m["n_positions"],
        n_embd=m["n_embd"], n_layer=2, n_head=m["n_head"],
        attention=tr["attention"], remat=tr["remat"],
        logits_dtype=jnp.bfloat16)
    opt = optax.chain(optax.clip_by_global_norm(tr["clip_norm"]),
                      optax.adamw(tr["lr"], b1=tr["b1"], b2=tr["b2"],
                                  weight_decay=tr["weight_decay"]))
    params = jax.eval_shape(
        lambda: gpt2.init_params(gcfg, jax.random.PRNGKey(0)))
    tokens = _s(int(mix["batch"]), int(mix["seq"]) + 1, dtype=jnp.int32)
    hlo = _compile(chip, gpt2.make_train_step(gcfg, opt), params,
                   jax.eval_shape(opt.init, params), tokens,
                   donate_argnums=(0, 1))
    found = _flash_calls(hlo, cfg, mix)
    assert len(found["flash_fwd"]) == 1, found
    assert len(found["flash_bwd"]) == 1, found


# ----------------------------------------------------------------------
# serve path: paged decode attention + in-place KV append
# ----------------------------------------------------------------------
# llama1b4's pool as ContinuousLlamaService sizes it (22 layers, block
# 16, 32 slots), Llama-3-8B's GQA head layout on the same pool, and the
# benchmark's `mistral-7b-v0.3-l16` engine at the table widths its
# cells reach (16 closed, 64 chat) and the widest it can (81 =
# `max_len` 1296 / 16: a multiple of no compute block)
_MISTRAL = dict(L=16, NB=4096, BS=16, KV=8, HD=128, B=64, H=32)
_POOLS = {
    "llama1b4": dict(L=22, NB=512, BS=16, KV=16, HD=128, B=32, W=11, H=16),
    "llama3-8b-gqa": dict(L=22, NB=512, BS=16, KV=8, HD=128, B=32, W=11,
                          H=32),
    "mistral-7b-l16-w16": dict(W=16, **_MISTRAL),
    "mistral-7b-l16-w64": dict(W=64, **_MISTRAL),
    "mistral-7b-l16-w81": dict(W=81, **_MISTRAL),
}


def _pool_args(L, NB, BS, KV, HD, B, W, H, int8):
    pool = _s(L, NB, BS, KV, HD, dtype=jnp.int8 if int8 else BF16)
    scale = _s(L, NB, BS, KV, dtype=jnp.float32) if int8 else None
    tables, pos = _s(B, W, dtype=jnp.int32), _s(B, dtype=jnp.int32)
    return pool, scale, tables, pos


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("heads", sorted(_POOLS))
def test_paged_decode_attention(chip, heads, int8):
    d = _POOLS[heads]
    pool, scale, tables, pos = _pool_args(int8=int8, **d)
    q = _s(d["B"], d["H"], d["HD"])

    if int8:
        def fn(q, kp, vp, ks, vs, tables, pos):
            return pa.paged_decode_attention(
                q, kp, vp, tables, pos, 3, k_scale=ks, v_scale=vs)

        hlo = _compile(chip, fn, q, pool, pool, scale, scale, tables, pos)
    else:
        def fn(q, kp, vp, tables, pos):
            return pa.paged_decode_attention(q, kp, vp, tables, pos, 3)

        hlo = _compile(chip, fn, q, pool, pool, tables, pos)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_kv_append(chip, int8):
    """The int8 row is the one Mosaic refused before this file existed
    (`[B, KV]` per-row scale blocks: last two dims neither tile-aligned
    nor the whole array)."""
    d = _POOLS["llama1b4"]
    pool, scale, tables, pos = _pool_args(int8=int8, **d)
    row = _s(d["B"], d["KV"], d["HD"], dtype=pool.dtype)

    if int8:
        srow = _s(d["B"], d["KV"], dtype=jnp.float32)

        def fn(kp, vp, ks, vs, kn, vn, kns, vns, tables, pos):
            return pa.paged_kv_append(
                kp, vp, kn, vn, tables, pos, 3, k_scale=ks, v_scale=vs,
                k_new_scale=kns, v_new_scale=vns)

        hlo = _compile(chip, fn, pool, pool, scale, scale, row, row,
                       srow, srow, tables, pos,
                       donate_argnums=(0, 1, 2, 3))
    else:
        def fn(kp, vp, kn, vn, tables, pos):
            return pa.paged_kv_append(kp, vp, kn, vn, tables, pos, 3)

        hlo = _compile(chip, fn, pool, pool, row, row, tables, pos,
                       donate_argnums=(0, 1))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_llama1b4_decode_step_rows_paged(chip, int8):
    """The engine's whole decode step at serving size: both kernels
    inside the layer scan, pools donated."""
    cfg = llama1b4()
    d = _POOLS["llama1b4"]
    pool, scale, tables, pos = _pool_args(int8=int8, **d)
    tok = _s(d["B"], dtype=jnp.int32)

    if int8:
        def fn(params, tok, kp, vp, ks, vs, tables, pos):
            return llama.decode_step_rows(
                cfg, params, tok, (kp, vp, ks, vs), pos, tables=tables)

        hlo = _compile(chip, fn, _bf16_params(cfg), tok, pool, pool,
                       scale, scale, tables, pos,
                       donate_argnums=(2, 3, 4, 5))
    else:
        def fn(params, tok, kp, vp, tables, pos):
            return llama.decode_step_rows(
                cfg, params, tok, (kp, vp), pos, tables=tables)

        hlo = _compile(chip, fn, _bf16_params(cfg), tok, pool, pool,
                       tables, pos, donate_argnums=(2, 3))
    assert hlo.count("tpu_custom_call") >= 2  # append + attention


# ----------------------------------------------------------------------
# `llama.generate`, the engine's oracle (`reference_check`): no kernels,
# but the two programs it is made of must fit and compile at size
# ----------------------------------------------------------------------
def test_llama1b4_prefill_and_decode_step(chip):
    cfg = llama1b4()
    B, T, M = 8, 128, 160
    params = _bf16_params(cfg)
    _compile(chip, lambda p, t: llama.prefill(cfg, p, t, M), params,
             _s(B, T, dtype=jnp.int32))
    cache = _s(cfg.n_layers, B, M, cfg.n_kv_heads, cfg.head_dim)
    _compile(
        chip,
        lambda p, tok, kc, vc, pos: llama.decode_step(
            cfg, p, tok, (kc, vc), pos),
        params, _s(B, dtype=jnp.int32), cache, cache, _s(dtype=jnp.int32),
        donate_argnums=(2, 3),
    )


# ----------------------------------------------------------------------
# serve path, latent attention + experts: `kanana-2-30b-a3b-l7`'s engine
# ----------------------------------------------------------------------
# one latent pool [7, 9217, 16, 640] (576 values lane-padded), 64 slots,
# 32 heads; table widths the cell reaches (32 ... 145 = `max_len` 2320 /
# 16, a multiple of no compute block)
_KANANA = dict(L=7, NB=9217, BS=16, D=640, B=64, H=32)


@pytest.mark.parametrize("W", [32, 145])
def test_mla_paged_kernels(chip, W):
    """A 576-wide page is refused ("slice shape ... must be aligned to
    tiling (128)"), which is why the pool is 640 wide; a one-row store
    at a dynamic offset into a `[16, 640]` bf16 page is refused too,
    which is why the append selects the row into the whole page."""
    d = _KANANA
    pool = _s(d["L"], d["NB"], d["BS"], d["D"])
    tables, pos = _s(d["B"], W, dtype=jnp.int32), _s(d["B"], dtype=jnp.int32)

    def attend(q, pool, tables, pos):
        return pa.mla_paged_decode_attention(
            q, pool, tables, pos, 3, value_dim=512, scale=192 ** -0.5)

    hlo = _compile(chip, attend, _s(d["B"], d["H"], 576), pool, tables, pos)
    assert "tpu_custom_call" in hlo and "bf16[64,32,512]" in hlo

    def append(pool, new, tables, pos):
        return pa.mla_paged_kv_append(pool, new, tables, pos, 3)

    hlo = _compile(chip, append, pool, _s(d["B"], 576), tables, pos,
                   donate_argnums=(0,))
    assert "tpu_custom_call" in hlo


# D, I, E, L, top_k of each model that serves through `dropless_moe`
_EXPERTS = {"kanana": (2048, 768, 128, 6, 6), "lfm2": (2048, 1792, 32, 14, 4)}


@pytest.mark.parametrize("model,tokens,decode", [
    ("kanana", 64, True), ("kanana", 2048, False),
    ("lfm2", 128, True), ("lfm2", 1296, False),
], ids=["kanana-decode", "kanana-n2048", "lfm2-decode", "lfm2-n1296"])
def test_dropless_expert_layer_with_grouped_kernel(chip, model, tokens,
                                                   decode):
    """The serving expert layer at kanana's and lfm2's widths.  A
    decode step (`row_mask`, 384 / 512 (token, expert) rows) keeps
    megablox's grouped product, which returns `bf16[slots * top_k,
    ...]`: what the benchmark's `moe_grouped` finds it by.  A prefill's
    three products are `ops/grouped_matmul`'s: both of a group's
    `[K, N]` slots in VMEM (14.7 MB at lfm2's widths, past Mosaic's
    default limit), the stack itself left in HBM."""
    from ray_tpu.parallel import moe

    D, I, E, L, top_k = _EXPERTS[model]
    layer = {"router": _s(D, E, dtype=jnp.float32),
             "router_bias": _s(E, dtype=jnp.float32),
             # whole stacks, as the layer scan hands them over: the kernel
             # picks its layer by index, no layer's experts are copied
             "e_gate": _s(L, E, D, I), "e_up": _s(L, E, D, I),
             "e_down": _s(L, E, I, D)}

    def fn(h, layer, index, mask):
        return moe.dropless_moe(h, layer, top_k=top_k, scale=2.448,
                                route_eps=1e-20, dtype=BF16, kernel=True,
                                stack_index=index,
                                row_mask=mask if decode else None)

    hlo = _compile(chip, fn, _s(tokens, D), layer, _s(dtype=jnp.int32),
                   _s(tokens, dtype=jnp.bool_))
    tm, ahead = moe.row_tiling(tokens * top_k, E)
    assert ahead != decode
    rows = -(-tokens * top_k // tm) * tm
    calls = [ln.split("=", 1)[1].lstrip() for ln in hlo.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 3
    assert sum(c.startswith(f"bf16[{rows},{I}]") for c in calls) == 2
    assert sum(c.startswith(f"bf16[{rows},{D}]") for c in calls) == 1
    assert all(("grouped_matmul_prefetch" in c) == ahead for c in calls)
    # no copy of a layer's experts beside the kernels
    assert f"bf16[{E},{D},{I}]" not in hlo and f"bf16[{E},{I},{D}]" not in hlo


# ----------------------------------------------------------------------
# admission: the packed prefill at both serve configurations' shapes
# ----------------------------------------------------------------------
def _mistral_l16() -> llama.LlamaConfig:
    return llama.LlamaConfig(
        vocab_size=32768, max_seq_len=32768, dim=4096, n_layers=16,
        n_heads=32, n_kv_heads=8, intermediate=14336, rope_theta=1e6,
        norm_eps=1e-5, dtype=BF16)


def _kanana_l7():
    from ray_tpu.models import deepseek_v3

    return deepseek_v3.DeepseekV3Config(n_layers=7, dtype=BF16)


@pytest.mark.parametrize("make,blocks,N", [
    (_mistral_l16, 4097, 1296), (_kanana_l7, 9217, 2320),
], ids=["mistral-7b-l16-n1296", "kanana-2-l7-n2320"])
def test_prefill_packed_at_the_cells_shapes(chip, make, blocks, N):
    """`prefill_packed_n<N>` as the engine jits it (the cache donated,
    16 rows a program, 64 slots, 16-token blocks) at the LARGEST size
    of each cell's closed set, a maximal sequence's blocks: 16 layers
    of Mistral-7B over two per-head pools; kanana's latent pool with
    the grouped expert products.  It holds no paged decode kernel: the
    benchmark tells decode programs from prefill by that."""
    from ray_tpu.models import deepseek_v3
    from ray_tpu.serve.engine_model import engine_model_for

    cfg = make()
    latent = isinstance(cfg, deepseek_v3.DeepseekV3Config)
    init = deepseek_v3.init_params if latent else llama.init_params
    params = jax.tree.map(
        lambda p: _s(*p.shape, dtype=p.dtype if latent else BF16),
        jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    model = engine_model_for(cfg, kv_dtype="model", block_size=16, chunk=8,
                             paged=True, interpret=False)
    cache = [_s(cfg.n_layers, blocks, 16, *leaf.tail, dtype=leaf.dtype)
             for leaf in model.cache_leaves]
    i32 = jnp.int32
    fn = model.prefill_packed(N)
    fn.__name__ = f"prefill_packed_n{N}"
    hlo = _compile(
        chip, fn, params, *cache, *[_s(N, dtype=i32)] * 3,
        _s(N // 16, dtype=i32), *[_s(16, dtype=i32)] * 4,
        *[_s(64, dtype=i32)] * 3,
        donate_argnums=tuple(range(1, 1 + len(cache))))
    assert f"jit_prefill_packed_n{N}" in hlo
    assert "input_output_alias" in hlo  # the pool is written in place
    # kanana's grouped products are kernels; Mistral's prefill has none
    assert ("tpu_custom_call" in hlo) == latent


# ----------------------------------------------------------------------
# power retention: the two kernels and the state model's two programs
# ----------------------------------------------------------------------
_BRUMBY = dict(L=5, B=32, H=40, KV=8, d=128)


def _brumby_l5():
    from ray_tpu.models import brumby

    return brumby.BrumbyConfig(n_layers=5, dtype=BF16)


def _state_leaves(d=_BRUMBY):
    from ray_tpu.ops import retention

    return [_s(*shape, dtype=jnp.float32) for shape in
            retention.state_shapes(d["L"], d["B"], d["KV"], d["d"])]


def _held(d=_BRUMBY, held=7):
    """A layer's `retention.Pending` at the cell's chunk of 8."""
    from ray_tpu.ops import retention

    kv = _s(d["B"], d["KV"], held, d["d"])
    return retention.Pending(kv, kv, _s(d["B"], d["KV"], held,
                                        dtype=jnp.float32),
                             _s(dtype=jnp.int32))


def _step_shapes(d=_BRUMBY):
    return (_s(d["B"], d["H"], d["d"]), _s(d["B"], d["KV"], d["d"]),
            _s(d["B"], d["KV"], d["d"]), _s(d["B"], d["KV"], dtype=jnp.float32))


@pytest.mark.parametrize("flush", [False, True], ids=["step", "flush"])
def test_retention_decode_kernel(chip, flush):
    """One `[65, 128, 128]` float32 state block a grid step (4.26 MB,
    two copies each way under a 64 MB VMEM limit), the state and the
    key sum aliased in place; the benchmark finds the kernel by its
    first result, the numerators `f32[32,8,128,128]`.  `flush`: the
    chunk's last step, 8 updates a block (7 held tokens and its own),
    the same results."""
    from ray_tpu.ops import retention

    def step(q, k, v, g, state, keysum, live, layer, pending):
        return retention.retention_decode(q, k, v, g, state, keysum, live,
                                          layer, eps=1e-6, kernel=True,
                                          pending=pending)

    hlo = _compile(
        chip, step, *_step_shapes(), *_state_leaves(),
        _s(_BRUMBY["B"], dtype=jnp.bool_), _s(dtype=jnp.int32),
        _held() if flush else None, donate_argnums=(4, 5))
    assert "tpu_custom_call" in hlo and "(f32[32,8,128,128]" in hlo
    assert "input_output_alias" in hlo


def test_retention_read_kernel(chip):
    """The step that writes no state: the state block and the key sum
    are inputs only (one copy in, none out), and its FIRST result is
    the denominators `f32[32,8,8,128]`, so a trace tells it from the
    flush, whose first result the benchmark's roofline reader looks
    for."""
    from ray_tpu.ops import retention

    def step(q, k, v, g, state, keysum, pending, live, layer):
        return retention.retention_read(q, k, v, g, state, keysum, pending,
                                        live, layer, eps=1e-6, kernel=True)

    hlo = _compile(
        chip, step, *_step_shapes(), *_state_leaves(), _held(),
        _s(_BRUMBY["B"], dtype=jnp.bool_), _s(dtype=jnp.int32))
    assert "tpu_custom_call" in hlo and "(f32[32,8,8,128]" in hlo
    assert "(f32[32,8,128,128]" not in hlo
    assert "f32[5,32,8,65,128,128]" in hlo and "input_output_alias" not in hlo


@pytest.mark.parametrize("N", [256, 2560])
def test_retention_prefill_kernel(chip, N):
    """The chunked scan at the smallest and the largest packed row of
    the cell's closed set, 256-token chunks: dynamic lane rotations, a
    tile of the carried state by its leading index, one-row stores of
    the key sum at a dynamic offset."""
    from ray_tpu.ops import retention

    d = _BRUMBY
    i32 = jnp.int32

    def scan(q, k, v, g, seg, posn, slots, state, keysum, layer):
        return retention.retention_prefill(
            q, k, v, g, seg, posn, slots, state, keysum, layer, chunk=256,
            eps=1e-6, kernel=True)

    hlo = _compile(
        chip, scan, _s(N, d["H"], d["d"]), _s(N, d["KV"], d["d"]),
        _s(N, d["KV"], d["d"]), _s(N, d["KV"], dtype=jnp.float32),
        _s(N, dtype=i32), _s(N, dtype=i32), _s(16, dtype=i32),
        *_state_leaves(), _s(dtype=i32), donate_argnums=(7, 8))
    assert "tpu_custom_call" in hlo and f"(bf16[8,5,{N},128]" in hlo
    assert "input_output_alias" in hlo


def test_state_model_programs_at_the_cells_shapes(chip):
    """`decode_chunk_state` and `prefill_packed_n2048` as the engine
    jits them for the retention model at the published widths (5
    layers, 32 slots, chunk 8, 256-token alignment): no tables, no
    `blk_ids`, the two state leaves donated and written in place."""
    from ray_tpu.models import brumby
    from ray_tpu.serve.engine_model import engine_model_for

    cfg = _brumby_l5()
    params = jax.tree.map(
        lambda p: _s(*p.shape, dtype=p.dtype),
        jax.eval_shape(lambda: brumby.init_params(cfg, jax.random.PRNGKey(0))))
    model = engine_model_for(cfg, kv_dtype="model", block_size=256, chunk=8,
                             paged=True, interpret=False)
    assert model.kv is None and [
        l.per_slot for l in model.cache_leaves] == [True, True]
    cache = _state_leaves()
    i32 = jnp.int32
    rows = [_s(32, dtype=i32)] * 3
    donate = dict(donate_argnums=(1, 2))
    fn = model.decode_chunk(0)
    fn.__name__ = "decode_chunk_state"
    hlo = _compile(chip, fn, params, *cache, *rows, **donate)
    assert "jit_decode_chunk_state" in hlo and "(f32[32,8,128,128]" in hlo
    assert "input_output_alias" in hlo
    # seven reads and one flush a chunk: both calls, and no step COPIES
    # a state leaf (5.5 GB) on its way through the two loops
    assert "(f32[32,8,8,128]" in hlo
    assert not [line for line in hlo.splitlines()
                if " copy(" in line
                and line.split("=", 1)[1].lstrip().startswith(
                    "f32[5,32,8,65,128,128]")]
    fn = model.prefill_packed(2048)
    fn.__name__ = "prefill_packed_n2048"
    hlo = _compile(chip, fn, params, *cache, *[_s(2048, dtype=i32)] * 3,
                   *[_s(16, dtype=i32)] * 4, *rows, **donate)
    assert "jit_prefill_packed_n2048" in hlo and "(bf16[8,5,2048,128]" in hlo
    assert "input_output_alias" in hlo and "(f32[32,8,128,128]" not in hlo


@pytest.mark.parametrize("name, config", [
    ("llama", "LlamaConfig"), ("deepseek_v3", "DeepseekV3Config"),
    ("lfm2", "Lfm2MoeConfig"), ("brumby", "BrumbyConfig")])
def test_a_model_that_names_no_last_step_traces_one_scan(name, config):
    """`chunk_program(last_step=None)` is ONE scan of `chunk` steps and
    nothing after it, the program every model but the retention model
    had before that argument (the dense model, the latent-MoE model,
    the hybrid whose per-slot leaves ride the same carry); the retention
    model's is a scan of `chunk - 1` reads, then the flush's layers
    once.  (Traced, not compiled: no chip is described.)"""
    import importlib

    from ray_tpu.serve.engine_model import engine_model_for

    mod = importlib.import_module(f"ray_tpu.models.{name}")
    cfg = getattr(mod, config).tiny()
    params = jax.eval_shape(
        lambda: mod.init_params(cfg, jax.random.PRNGKey(0)))
    model = engine_model_for(cfg, kv_dtype="model", block_size=8, chunk=4,
                             paged=False, interpret=False)
    slots = 2
    cache = [_s(l.layers or cfg.n_layers,
                *((slots,) if l.per_slot else (3, 8)), *l.tail, dtype=l.dtype)
             for l in model.cache_leaves]
    tables = [_s(slots, 1, dtype=jnp.int32)] if model.kv is not None else []
    rows = [_s(slots, dtype=jnp.int32)] * 3
    jaxpr = jax.make_jaxpr(model.decode_chunk(1))(
        params, *cache, *tables, *rows)
    loops = [e.params["length"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "scan"]
    if name == "brumby":
        # three reads scanned, then the flush's scan over the layers
        assert loops == [3, cfg.n_layers]
    else:
        # the chunk's scan alone: the layers' scans lie inside its body
        assert loops == [4]


# ----------------------------------------------------------------------
# the hybrid (LFM2-8B-A1B cut to 16 layers): head width 64, both caches
# ----------------------------------------------------------------------
# the benchmark's `lfm2-8b-a1b-l16` engine: 4 attention layers' pools,
# 10,368 blocks + scratch, 128 slots, the widest table (81 = 1296 / 16)
_LFM2_POOL = dict(L=4, NB=10369, BS=16, KV=8, HD=64, B=128, H=32)


def _lfm2_l16():
    from ray_tpu.models import lfm2

    return lfm2.Lfm2MoeConfig(layer_types=lfm2.LAYER_TYPES[:16],
                              max_seq_len=1296)


@pytest.mark.parametrize("W", [16, 81])
def test_paged_kernels_at_head_width_64(chip, W):
    """A page `[16 x 8, 64]` would be half a lane tile wide: the pool
    holds a token's heads side by side in one row of whole lanes
    (`kv_pool_tail`: a page `[16, 512]`, the same bytes), and the decode
    kernel and the in-place append run on it as the hybrid's attention
    layers call them, 128 rows, queries and new rows still 64 wide."""
    d = dict(W=W, **_LFM2_POOL)
    assert pa.kv_pool_tail(d["KV"], d["HD"]) == (512,)
    assert pa.kv_pool_tail(8, 128) == (8, 128)
    pool = _s(d["L"], d["NB"], d["BS"], 512)
    tables, pos = _s(d["B"], W, dtype=jnp.int32), _s(d["B"], dtype=jnp.int32)
    q, row = _s(d["B"], d["H"], d["HD"]), _s(d["B"], d["KV"], d["HD"])

    def fn(q, kp, vp, kn, vn, tables, pos, layer):
        kp, vp = pa.paged_kv_append(kp, vp, kn, vn, tables, pos, layer)
        return pa.paged_decode_attention(q, kp, vp, tables, pos, layer), kp, vp

    hlo = _compile(chip, fn, q, pool, pool, row, row, tables, pos,
                   _s(dtype=jnp.int32), donate_argnums=(1, 2))
    assert hlo.count("tpu_custom_call") >= 2 and "bf16[128,32,512]" in hlo
    assert "input_output_alias" in hlo


def test_hybrid_model_programs_at_the_cells_shapes(chip):
    """`decode_chunk_w81` and `prefill_packed_n1296` as the engine jits
    them for the hybrid at the published widths (16 layers: 12
    convolution + 4 attention, 32 experts, 128 slots, chunk 8): tables
    AND a per-slot leaf in one signature, all three leaves donated and
    written in place, and no copy of a layer's experts beside the
    grouped kernels."""
    from ray_tpu.models import lfm2
    from ray_tpu.serve.engine_model import engine_model_for

    cfg = _lfm2_l16()
    params = jax.tree.map(
        lambda p: _s(*p.shape, dtype=p.dtype),
        jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0))))
    model = engine_model_for(cfg, kv_dtype="model", block_size=16, chunk=8,
                             paged=True, interpret=False)
    assert [(l.per_slot, l.layers) for l in model.cache_leaves] == [
        (False, 4), (False, 4), (True, 12)]
    d = _LFM2_POOL
    pool = _s(d["L"], d["NB"], d["BS"], 512)
    cache = [pool, pool, _s(12, d["B"], 3 * 2048)]
    i32 = jnp.int32
    rows = [_s(d["B"], dtype=i32)] * 3
    donate = dict(donate_argnums=(1, 2, 3))
    fn = model.decode_chunk(81)
    fn.__name__ = "decode_chunk_w81"
    hlo = _compile(chip, fn, params, *cache, _s(d["B"], 81, dtype=i32),
                   *rows, **donate)
    assert "jit_decode_chunk_w81" in hlo and "bf16[128,32,512]" in hlo
    assert "input_output_alias" in hlo
    assert "bf16[32,2048,1792]" not in hlo and "bf16[32,1792,2048]" not in hlo
    fn = model.prefill_packed(1296)
    fn.__name__ = "prefill_packed_n1296"
    hlo = _compile(chip, fn, params, *cache, *[_s(1296, dtype=i32)] * 3,
                   _s(81, dtype=i32), *[_s(16, dtype=i32)] * 4, *rows,
                   **donate)
    assert "jit_prefill_packed_n1296" in hlo and "input_output_alias" in hlo
    assert "bf16[32,2048,1792]" not in hlo and "bf16[32,1792,2048]" not in hlo


# ----------------------------------------------------------------------
# the window-and-full model: keys 192 and values 128 wide, folded pools
# of two widths beside a per-slot ring of window rows
# ----------------------------------------------------------------------
def _mimo_l7():
    from ray_tpu.models import mimo_v2

    full = mimo_v2.MimoV2Config()
    return dataclasses.replace(
        full, layer_pattern=full.layer_pattern[:7],
        moe_layers=full.moe_layers[:7], experts_held=16, vocab_size=19072)


@pytest.mark.parametrize("W", [128, 545])
def test_paged_kernels_at_keys_192_and_values_128(chip, W):
    """The full layers' folded pools: a K page `[16, 4 x 192 = 768]` and
    a V page `[16, 4 x 128 = 512]`, 64 query heads against them at 128
    rows; the queries and the result lie whole in VMEM (42 MB in their
    two buffers each), so the call asks for its own limit."""
    B, H, NB = 128, 64, 4096
    kp, vp = _s(2, NB, 16, 768), _s(2, NB, 16, 512)
    tables, pos = _s(B, W, dtype=jnp.int32), _s(B, dtype=jnp.int32)

    def fn(q, kp, vp, kn, vn, tables, pos, layer):
        kp, vp = pa.paged_kv_append(kp, vp, kn, vn, tables, pos, layer)
        return pa.paged_decode_attention(q, kp, vp, tables, pos, layer), kp, vp

    hlo = _compile(chip, fn, _s(B, H, 192), kp, vp, _s(B, 768), _s(B, 512),
                   tables, pos, _s(dtype=jnp.int32), donate_argnums=(1, 2))
    assert hlo.count("tpu_custom_call") >= 2 and "bf16[128,64,512]" in hlo
    assert "input_output_alias" in hlo


def test_window_full_model_programs_at_the_cells_shapes(chip):
    """`decode_chunk_w545`, `prefill_packed_n2048` and
    `prefill_chunk_n2048` as the engine jits them for MiMo-V2.5's cut at
    the published widths (7 layers: 2 full + 5 window, 16 of 256 experts
    held, 128 slots, chunk 8): tables and per-slot rings in one
    signature, all four leaves donated and written in place, the paged
    kernel at 768 / 512 lanes inside the decode program."""
    from ray_tpu.models import mimo_v2
    from ray_tpu.serve.engine_model import engine_model_for

    cfg = _mimo_l7()
    params = jax.tree.map(
        lambda p: _s(*p.shape, dtype=p.dtype),
        jax.eval_shape(lambda: mimo_v2.init_params(cfg, jax.random.PRNGKey(0))))
    model = engine_model_for(cfg, kv_dtype="model", block_size=16, chunk=8,
                             paged=True, interpret=False)
    assert [(l.per_slot, l.layers) for l in model.cache_leaves] == [
        (False, 2), (False, 2), (True, 5), (True, 5)]
    B, NB, W, N = 128, 40961, 545, 2048
    cache = [_s(2, NB, 16, 768), _s(2, NB, 16, 512),
             _s(5, B, 128, 1536), _s(5, B, 128, 1024)]
    i32 = jnp.int32
    rows, one = [_s(B, dtype=i32)] * 3, _s(dtype=i32)
    donate = dict(donate_argnums=(1, 2, 3, 4))
    fn = model.decode_chunk(W)
    fn.__name__ = "decode_chunk_w545"
    hlo = _compile(chip, fn, params, *cache, _s(B, W, dtype=i32), *rows,
                   **donate)
    assert "jit_decode_chunk_w545" in hlo and "bf16[128,64,512]" in hlo
    assert "input_output_alias" in hlo
    # a step's 1,024 pairs stay pair-wide: every pair's row in and out
    assert "bf16[1024,4096]" in hlo
    fn = model.prefill_packed(N)
    fn.__name__ = "prefill_packed_n2048"
    hlo = _compile(chip, fn, params, *cache, *[_s(N, dtype=i32)] * 3,
                   _s(N // 16, dtype=i32), *[_s(16, dtype=i32)] * 4, *rows,
                   **donate)
    assert "jit_prefill_packed_n2048" in hlo and "input_output_alias" in hlo
    _holds_slab_rows_only(hlo)
    _folds_the_full_layers_in_one_kernel_each(hlo)
    fn = model.chunk_prefill(N)
    fn.__name__ = "prefill_chunk_n2048"
    hlo = _compile(chip, fn, params, *cache, _s(N, dtype=i32),
                   _s(W, dtype=i32), *[one] * 6, *rows, **donate)
    assert "jit_prefill_chunk_n2048" in hlo and "input_output_alias" in hlo
    # never a [chunk, context] score array whole
    assert "f32[4,16,2048,8720]" not in hlo
    _holds_slab_rows_only(hlo)
    _folds_the_full_layers_in_one_kernel_each(hlo)


def _folds_the_full_layers_in_one_kernel_each(hlo):
    """A 2,048-token admission program under the `paged` route: each of
    the two full layers' attention is ONE `prefill_attention` call under
    the `full_attn` scope (where `window_full_prefill_attn_ms` finds it)
    and no key block's scores `[KV, G, N, 512]` exist as an array.  The
    benchmark tells this cell's kernels apart by what they return: the
    call gives back `bf16[4,16,2048,128]`, not the paged decode kernel's
    `bf16[128,64,512]`, and aliases no operand, as the append does."""
    assert "f32[4,16,2048,512]" not in hlo and "f32[4,16,2048,1024]" not in hlo
    calls = [ln.split("=", 1)[1].lstrip() for ln in hlo.splitlines()
             if " = " in ln and "tpu_custom_call" in ln
             and "/full_attn/" in ln]
    assert len(calls) == 2
    for c in calls:
        assert c.startswith("bf16[4,16,2048,128]") and "prefill_attention" in c
        assert "output_to_operand_aliasing" not in c
    assert not [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
                and ln.split("=", 1)[1].lstrip().startswith("bf16[128,64,512]")]


def _holds_slab_rows_only(hlo):
    """A 2,048-token admission program's expert layers move the held
    pairs alone (`parallel/moe.dropless_moe(held=)` from `COMPACT_FROM`
    pairs on): no array over all 16,384 (token, expert) pairs at the
    model's width, none `[tokens, top_k, D]` in float32, and the slab's
    2,048 rows (twice the mean 1,024 that fall to 16 of 256 experts) in
    and out of the grouped products, whose tile is 64 rows."""
    from ray_tpu.parallel import moe

    assert moe.slab_rows(2048 * 8, 16, 256) == 2048
    # what the expert layers compute (the dense layer's `w_down` is a
    # `bf16[16384,4096]` too): the ops traced under their scope
    made = [ln.split("=", 1)[1].lstrip() for ln in hlo.splitlines()
            if " = " in ln and "/moe_routed/" in ln]
    for pair_wide in ("bf16[16384,4096]", "bf16[16384,2048]",
                      "f32[16384,4096]", "f32[2048,8,4096]"):
        assert not [c for c in made if c.startswith(pair_wide)], pair_wide
    assert [c for c in made if c.startswith("bf16[2048,4096]")]
    calls = [c for c in made if "grouped_matmul_prefetch" in c
             and "custom-call(" in c]
    assert len(calls) == 3 * 6
    assert sum(c.startswith("bf16[2048,2048]") for c in calls) == 2 * 6
    assert sum(c.startswith("bf16[2048,4096]") for c in calls) == 6


# ----------------------------------------------------------------------
# the recurrent model: a per-slot float32 state, one attention layer in
# eleven, a share of a latent expert layer
# ----------------------------------------------------------------------
def _nemotron3s_l11():
    from ray_tpu.models import nemotron_h

    return nemotron_h.NemotronHConfig(vocab_size=32768, experts_held=128)


@pytest.mark.parametrize("K,resumes", [(1, True), (16, False), (1, False),
                                       (16, True)])
def test_ssd_scan_kernel(chip, K, resumes):
    """The Mamba-2 scan of one layer at the cell's shapes (a row of
    2,048 tokens, 128 heads of 64 in 8 groups, a state of 128, chunks of
    128): a chunk of a long prompt that RESUMES from a state (K 1) and a
    packed row of 16 prompts from zero, as the two admission programs
    call it, and the two other pairings.  Sixteen heads a grid step, two
    side by side on a tile of lanes; `y` and every chunk's start state
    come back with the heads on the lanes."""
    from ray_tpu.ops import ssd

    T, H, P, G, N = 2048, 128, 64, 8, 128
    f32, i32 = jnp.float32, jnp.int32

    def scan(x, dt, A, B, C, seg, ends, *init):
        return ssd.ssd_scan(x, dt, A, B, C, seg, ends,
                            init=init[0] if init else None, kernel=True)

    hlo = _compile(
        chip, scan, _s(T, H, P), _s(T, H, dtype=f32), _s(H, dtype=f32),
        _s(T, G, N), _s(T, G, N), _s(T, dtype=i32), _s(K, dtype=i32),
        *([_s(H, P, N, dtype=f32)] if resumes else []))
    assert hlo.count("tpu_custom_call") == 1
    assert "(f32[16,128,8192]" in hlo and "f32[16,128,128,128]" not in hlo
    assert f"f32[{K},128,64,128]" in hlo


def test_recurrent_model_programs_at_the_cells_shapes(chip):
    """`decode_chunk_w545`, `prefill_packed_n2048` and
    `prefill_chunk_n2048` as the engine jits them for Nemotron 3 Super's
    cut at the published widths (11 layers `MEMEMEM*EME`, 128 of 512
    experts held, 128 slots, chunk 8): the table and both per-slot
    states in one signature, all four leaves donated and written in
    place, the paged kernel on the folded 256-lane pool inside the
    decode program, the grouped products of the two-matrix experts in a
    1,024-wide latent (22 pairs a token: 2,816 a decode step, pair-wide;
    45,056 a chunk, the held pairs alone in slabs of 22,528 rows), and
    the scan of each Mamba layer as one kernel in both admission
    programs."""
    from ray_tpu.models import nemotron_h
    from ray_tpu.parallel import moe
    from ray_tpu.serve.engine_model import engine_model_for

    cfg = _nemotron3s_l11()
    params = jax.tree.map(
        lambda p: _s(*p.shape, dtype=p.dtype),
        jax.eval_shape(lambda: nemotron_h.init_params(
            cfg, jax.random.PRNGKey(0))))
    model = engine_model_for(cfg, kv_dtype="model", block_size=16, chunk=8,
                             paged=True, interpret=False)
    assert [(l.per_slot, l.layers) for l in model.cache_leaves] == [
        (False, 1), (False, 1), (True, 5), (True, 5)]
    B, NB, W, N = 128, 40961, 545, 2048
    cache = [_s(1, NB, 16, 256), _s(1, NB, 16, 256),
             _s(5, B, 128, 64, 128, dtype=jnp.float32), _s(5, B, 30720)]
    i32 = jnp.int32
    rows, one = [_s(B, dtype=i32)] * 3, _s(dtype=i32)
    donate = dict(donate_argnums=(1, 2, 3, 4))
    fn = model.decode_chunk(W)
    fn.__name__ = "decode_chunk_w545"
    hlo = _compile(chip, fn, params, *cache, _s(B, W, dtype=i32), *rows,
                   **donate)
    assert "jit_decode_chunk_w545" in hlo and "input_output_alias" in hlo
    # the paged kernel's result: 32 heads on the folded 2 x 128 lanes
    assert "bf16[128,32,256]" in hlo
    # a step's 2,816 pairs stay pair-wide, in the latent width
    assert "bf16[2816,1024]" in hlo and "bf16[2816,4096]" not in hlo
    for name in ("ssm_step", "ssm_conv", "ssm_proj", "latent_moe_routed",
                 "latent_moe_proj", "moe_shared", "full_attn"):
        assert f"/{name}/" in hlo, name
    assert moe.slab_rows(N * 22, 128, 512) == 22528
    for fn, name, host in (
            (model.prefill_packed(N), "prefill_packed_n2048",
             [*[_s(N, dtype=i32)] * 3, _s(N // 16, dtype=i32),
              *[_s(16, dtype=i32)] * 4]),
            (model.chunk_prefill(N), "prefill_chunk_n2048",
             [_s(N, dtype=i32), _s(W, dtype=i32), *[one] * 6])):
        fn.__name__ = name
        hlo = _compile(chip, fn, params, *cache, *host, *rows, **donate)
        assert f"jit_{name}" in hlo and "input_output_alias" in hlo
        # the attention layer folds in ONE kernel; no score array whole
        calls = [ln.split("=", 1)[1].lstrip() for ln in hlo.splitlines()
                 if " = " in ln and "tpu_custom_call" in ln
                 and "/full_attn/" in ln]
        assert len(calls) == 1 and calls[0].startswith("bf16[2,16,2048,128]")
        assert "f32[2,16,2048,8720]" not in hlo
        # the held pairs alone, two grouped products a slab and layer
        made = [ln.split("=", 1)[1].lstrip() for ln in hlo.splitlines()
                if " = " in ln and "/latent_moe_routed/" in ln]
        assert not [c for c in made if c.startswith("bf16[45056,")]
        gmm = [c for c in made if "grouped_matmul_prefetch" in c
               and "custom-call(" in c]
        assert len(gmm) == 2 * 5
        assert sum(c.startswith("bf16[22528,2688]") for c in gmm) == 5
        assert sum(c.startswith("bf16[22528,1024]") for c in gmm) == 5
        # the scan: ONE kernel a Mamba layer, whose float32 state is
        # carried between its 16 chunks in VMEM; no decay array whole
        scans = [ln for ln in hlo.splitlines() if " = " in ln
                 and "tpu_custom_call" in ln and "/ssm_scan/" in ln]
        assert len(scans) == 5, name
        assert "f32[16,128,128,128]" not in hlo


# ----------------------------------------------------------------------
# the block-diffusion model: a block of B rows a slot a forward
# ----------------------------------------------------------------------
# the benchmark's `sdar-30b-a3b-chat-l6` engine: 6 layers' folded pools
# (4 heads of 128 side by side), 10,368 blocks + scratch, 128 slots
_SDAR_POOL = dict(L=6, NB=10369, BS=16, HD=512, B=128)


def _sdar_l6():
    from ray_tpu.models import sdar

    return sdar.SdarMoeConfig(n_layers=6, max_seq_len=1296)


@pytest.mark.parametrize("W", [32, 81])
def test_paged_kernels_take_a_block_of_rows_a_slot(chip, W):
    """A block step's two calls at the cell's shapes: `paged_kv_append(
    rows=4)` writes four consecutive rows a slot into one page of the
    folded pool, and the decode kernel reads them back for `KV x (B x
    G)` = 128 query heads of one row."""
    d = _SDAR_POOL
    pool = _s(d["L"], d["NB"], d["BS"], d["HD"])
    tables, pos = _s(d["B"], W, dtype=jnp.int32), _s(d["B"], dtype=jnp.int32)
    q, new = _s(d["B"], 128, 128), _s(d["B"], 4, d["HD"])

    def fn(q, kp, vp, kn, vn, tables, pos, layer):
        kp, vp = pa.paged_kv_append(kp, vp, kn, vn, tables, pos, layer,
                                    rows=4)
        return (pa.paged_decode_attention(q, kp, vp, tables, pos + 3, layer),
                kp, vp)

    hlo = _compile(chip, fn, q, pool, pool, new, new, tables, pos,
                   _s(dtype=jnp.int32), donate_argnums=(1, 2))
    assert hlo.count("tpu_custom_call") >= 2 and "bf16[128,128,512]" in hlo
    assert "input_output_alias" in hlo


def _decode_walk(fn, *args):
    """(the walk's VMEM tiles, the VMEM the call asks for or None) of
    the one `paged_decode_attention` kernel `fn` holds, from its jaxpr."""
    calls = [e for e in jax.make_jaxpr(fn)(*args).eqns
             if e.primitive.name == "pallas_call"
             and e.params["name"] == "paged_decode_attention"]
    assert len(calls) == 1
    p = calls[0].params
    tiles = [a.inner_aval for a in p["grid_mapping"].scratch_avals
             if a.inner_aval.ndim == 3]
    return tiles, p["compiler_params"]["mosaic_tpu"].vmem_limit_bytes


# the three cells whose pools are FOLDED, as their decode programs call
# the kernel: slots, query heads, query width, K and V lanes, the widest
# table, and the tokens a block of the walk then holds
_FOLDED_WALKS = {
    "mimo25_mixed_closed_8k": (128, 64, 192, 768, 512, 545, 512),
    "lfm2_batch_closed_512": (128, 32, 64, 512, 512, 81, 512),
    "sdar30b_blockgen_closed_512": (128, 128, 128, 512, 512, 81, 256),
}


@pytest.mark.parametrize("cell", sorted(_FOLDED_WALKS))
def test_folded_walks_lower_at_their_longest_block(chip, cell):
    """A folded pool is one kv head to the kernel, so its block is the
    longest the score tile allows (`pa._pages_per_block`): 512 tokens at
    up to 64 query heads, 256 at 128.  Each cell's call holds K and V
    tiles of that many rows, two of each; the VMEM it asks for, where it
    asks, covers them beside the queries and the result in their two
    buffers each; and Mosaic takes the call for the described chip (a
    call that asks for none lowers under Mosaic's own limit)."""
    B, H, hd, lanes_k, lanes_v, W, T = _FOLDED_WALKS[cell]
    args = (_s(B, H, hd), _s(2, 4096, 16, lanes_k), _s(2, 4096, 16, lanes_v),
            _s(B, W, dtype=jnp.int32), _s(B, dtype=jnp.int32))

    def fn(q, kp, vp, tables, pos):
        return pa.paged_decode_attention(q, kp, vp, tables, pos, 1)

    tiles, ask = _decode_walk(fn, *args)
    assert [t.shape for t in tiles] == [(2, T, lanes_k), (2, T, lanes_v)]
    held = (2 * B * H * (lanes_k + lanes_v) * 2
            + sum(t.size * t.dtype.itemsize for t in tiles))
    if ask is None:
        assert held <= pa._VMEM_DEFAULT_BYTES
    else:
        assert ask >= held
    hlo = _compile(chip, fn, *args)
    assert "tpu_custom_call" in hlo and f"bf16[{B},{H},{lanes_v}]" in hlo


def test_block_diffusion_programs_at_the_cells_shapes(chip):
    """`decode_chunk_w81` (the block chunk program: 8 forwards of 128
    slots x 4 positions) and `prefill_packed_n1296` as the engine jits
    them for SDAR-30B-A3B's cut at the published widths (6 layers, 128
    experts, the whole vocabulary): both pools donated and written in
    place, no copy of a layer's experts beside the grouped kernels, and
    the row state `[128, 20]` where a one-token model's `tok` stands;
    a forward's two halves are two calls of the attention kernel, each
    of the shape the benchmark finds it by."""
    from ray_tpu.models import sdar
    from ray_tpu.serve.engine_model import engine_model_for

    cfg = _sdar_l6()
    params = jax.tree.map(
        lambda p: _s(*p.shape, dtype=p.dtype),
        jax.eval_shape(lambda: sdar.init_params(cfg, jax.random.PRNGKey(0))))
    model = engine_model_for(cfg, kv_dtype="model", block_size=16, chunk=8,
                             paged=True, interpret=False)
    assert (model.advance, model.reach, model.token_rows) == (32, 32, 66)
    d = _SDAR_POOL
    pool = _s(d["L"], d["NB"], d["BS"], d["HD"])
    i32 = jnp.int32
    rows = [_s(d["B"], dtype=i32)] * 2
    state = _s(d["B"], 20, dtype=i32)
    donate = dict(donate_argnums=(1, 2))
    fn = model.decode_chunk(81)
    fn.__name__ = "decode_chunk_w81"
    hlo = _compile(chip, fn, params, pool, pool, _s(d["B"], 81, dtype=i32),
                   state, *rows, **donate)
    assert "jit_decode_chunk_w81" in hlo and "bf16[128,128,512]" in hlo
    assert "input_output_alias" in hlo and "s32[73,128]" in hlo
    assert "bf16[256,128,512]" not in hlo
    assert "bf16[128,2048,768]" not in hlo and "bf16[128,768,2048]" not in hlo
    fn = model.prefill_packed(1296)
    fn.__name__ = "prefill_packed_n1296"
    hlo = _compile(chip, fn, params, pool, pool, *[_s(1296, dtype=i32)] * 3,
                   _s(81, dtype=i32), *[_s(16, dtype=i32)] * 4,
                   _s(16, dtype=i32), _s(16, dtype=jnp.float32),
                   rows[0], state, rows[1], **donate)
    assert "jit_prefill_packed_n1296" in hlo and "input_output_alias" in hlo
    assert "bf16[128,2048,768]" not in hlo and "bf16[128,768,2048]" not in hlo


# ----------------------------------------------------------------------
# the trained sparse cell: grouped, windowed flash kernels and the
# grouped product with its backward, at `trinity_mini_train_8k`'s shapes
# ----------------------------------------------------------------------
def _sparse_train_cell():
    from benchmarks import manifest

    cell = manifest.cell("trinity_mini_train_8k")
    return manifest.config(cell["config"]), manifest.traffic(cell["traffic"])


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_grouped_windowed_flash_lowers_at_the_cells_shapes(chip, window):
    """32 query heads over 4 KV heads of 128 at 8,192 tokens: the
    forward (its log-sum-exp transposed to a row of lanes in the
    kernel) and the split backward, under the names the cell's plane
    looks for; and no `flash_fwd` / `flash_bwd` of GPT-2's readers."""
    from benchmarks.planes import train_window_moe as plane

    cfg, mix = _sparse_train_cell()
    m = cfg["model"]
    B, T = int(mix["batch"]), int(mix["seq"])
    H, KV, D = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])

    def grads(q, k, v, do):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, True, 1024, 1024, False, window).astype(jnp.float32)
            * do), (0, 1, 2))(q, k, v)

    hlo = _compile(chip, grads, _s(B, T, H, D), _s(B, T, KV, D),
                   _s(B, T, KV, D), _s(B, T, H, D, dtype=jnp.float32))
    calls = [l.strip() for l in hlo.splitlines() if " custom-call(" in l
             and "tpu_custom_call" in l]
    preds = plane.kernel_predicates(cfg, mix)
    found = {k: [c for c in calls if p(c)] for k, p in preds.items()}
    assert len(found["afmoe_flash_fwd"]) == 1
    assert len(found["afmoe_flash_bwd"]) == 2    # dq, and dk with dv
    assert not found["afmoe_gmm"] and not found["afmoe_tgmm"]
    # the forward's second result is rows of lanes, not `[.., T, 1]`
    assert f"f32[{B * KV},{T // 256},1,2048]" in found["afmoe_flash_fwd"][0]


def test_the_grouped_product_and_its_backward_lower(chip):
    """One slab of the cell's held experts (16 of 2,048 x 1,024, 16,384
    sorted rows): `gmm` forward, `gmm` against the matrices transposed
    for the rows' gradient, megablox's `tgmm` for the matrices' in
    float32, each under the name the cell's plane looks for."""
    from benchmarks.planes import train_window_moe as plane
    from ray_tpu.ops.grouped_matmul import grouped_product
    from ray_tpu.parallel import moe

    cfg, mix = _sparse_train_cell()
    m = cfg["model"]
    E, D, I = m["num_experts"], m["hidden_size"], m["moe_intermediate_size"]
    rows = moe.train_slab_rows(
        int(mix["batch"]) * int(mix["seq"]) * m["num_experts_per_tok"], E,
        cfg["deployment"]["router_experts"])
    assert rows == 16384

    def grads(xs, gate, down, sizes, dy):
        def f(xs, gate, down):
            act = grouped_product(xs, gate, sizes, moe.TRAIN_ROW_TILE)
            return jnp.sum(grouped_product(
                jax.nn.silu(act), down, sizes, moe.TRAIN_ROW_TILE
            ).astype(jnp.float32) * dy)
        return jax.grad(f, (0, 1, 2))(xs, gate, down)

    hlo = _compile(
        chip, grads, _s(rows, D), _s(E, D, I, dtype=jnp.float32),
        _s(E, I, D, dtype=jnp.float32), _s(E, dtype=jnp.int32),
        _s(rows, D, dtype=jnp.float32))
    calls = [l.strip() for l in hlo.splitlines() if " custom-call(" in l
             and "tpu_custom_call" in l]
    preds = plane.kernel_predicates(cfg, mix)
    # the first product forward (the last one's VALUE is not needed by
    # its gradient), and both against the matrices transposed
    assert len([c for c in calls if preds["afmoe_gmm"](c)]) == 3
    tg = [c for c in calls if preds["afmoe_tgmm"](c)]
    assert len(tg) == 2 and all(f"f32[{E}," in c for c in tg)
