"""The main path's Pallas kernels, compiled for a TPU v5e chip that is
described and not attached (libtpu's compiler runs on the CPU sandbox).

Interpret mode checks a kernel's arithmetic and none of the TPU
lowering's rules: the paged-attention kernel passed every interpret test
while no serving decode step could compile for a TPU (its K/V block
``(1, page, 1, d)`` broke the last-two-block-dims rule), and the flash
kernel under an fsdp x mp mesh was refused as "cannot be automatically
partitioned". These compiles, at Llama-3-8B and serving-bench widths,
keep both repaired and guard every later PR at no chip time. Nothing
runs, so they say nothing about results or speed — on-chip parity is
tests/test_paged_attention_tpu.py, run by tools/run_tpu_checks.py.

All of it lives in ONE file and the topology is described inside a
fixture: only one process may load the TPU library, so no import, skipif
or parametrize argument may touch it (each xdist worker imports every
test file; only the worker that is handed this file loads libtpu).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import (flash_attention, fused_norm, moe_routing,
                                   paged_attention, selective_scan)

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device can be written to the persistent
    cache but not read back without a chip; keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_persistent_cache):
    """Steer every kernel module off interpret mode IN THE TEST (the
    default backend here is the CPU; each module binds its own name)."""
    for mod in (flash_attention, paged_attention, fused_norm, moe_routing,
                selective_scan):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(text: str, name: str) -> int:
    """Mosaic custom calls of the pallas_call named ``name`` (its scope in
    op_name, bare or inside jvp()/transpose() wrappers)."""
    scope = re.compile(rf"\b{name}\)*/pallas_call")
    return sum(1 for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and scope.search(line))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("heads,kv_heads",
                         [(32, 8), (16, 8), (32, 2), (20, 1)],
                         ids=["llama3_8b", "serving_bench", "nemotron_h",
                              "jamba"])
def test_paged_attention_compiles(mosaic, one_chip, heads, kv_heads, quant):
    slots, page, d, pages, table = 8, 16, 128, 512, 32

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = S((slots, 1, heads, d), BF16)
    tables, lens = S((slots, table), I32), S((slots,), I32)
    assert paged_attention.kernel_applicable(q.shape,
                                             (pages, page, kv_heads, d))
    if quant:
        codes = S((pages, page, kv_heads, d), I8)
        scales = S((pages, page, kv_heads), F32)
        text = _compile(
            lambda q, k, v, t, n, ks, vs: paged_attention.paged_attention_tpu(
                q, k, v, t, n, k_scale=ks, v_scale=vs),
            q, codes, codes, tables, lens, scales, scales)
    else:
        pool = S((pages, page, kv_heads, d), BF16)
        text = _compile(paged_attention.paged_attention_tpu,
                        q, pool, pool, tables, lens)
    assert _kernels(text, paged_attention.KERNEL_NAME) == 1


@pytest.mark.parametrize("shape", [(1, 4096, 32, 128), (4, 2048, 16, 128)],
                         ids=["llama3_8b_seq4096", "bench_seq2048"])
def test_flash_attention_forward_compiles(mosaic, one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    text = _compile(
        lambda q, k, v: flash_attention.flash_attention(q, k, v, causal=True),
        x, x, x)
    assert _kernels(text, "flash_attention_fwd") == 1


@pytest.mark.parametrize("shape", [(1, 4096, 32, 128), (4, 2048, 16, 128)],
                         ids=["llama3_8b_seq4096", "bench_seq2048"])
def test_flash_attention_backward_compiles(mosaic, one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(F32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert _kernels(text, "flash_attention_fwd") == 1
    assert _kernels(text, "flash_attention_bwd_dq") == 1
    assert _kernels(text, "flash_attention_bwd_dkv") == 1


@pytest.mark.parametrize("shape", [(1, 4096, 4096), (8, 1, 4096),
                                   (8, 64, 4096)],
                         ids=["prefill_rows", "decode_rows", "chunk_rows"])
def test_fused_rms_norm_compiles(mosaic, one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    w = jax.ShapeDtypeStruct(shape[-1:], BF16, sharding=one_chip)

    def fwd_and_grads(x, w):
        y, vjp = jax.vjp(fused_norm.fused_rms_norm, x, w)
        return y, vjp(y)

    assert _kernels(_compile(fwd_and_grads, x, w), "fused_rms_norm") == 1


def test_moe_top2_routing_compiles(mosaic, one_chip):
    tokens, experts, capacity = 8192, 16, 1280     # the qwen2_moe bench
    assert moe_routing.fused_routing_applicable(tokens, experts)
    logits = jax.ShapeDtypeStruct((tokens, experts), F32, sharding=one_chip)
    text = _compile(
        lambda lg: moe_routing.fused_top2_routing(lg, None, capacity,
                                                  False, 0.01), logits)
    assert _kernels(text, "moe_top2_routing") == 1


def test_flash_attention_under_fsdp_mp_mesh_compiles(mosaic, topo,
                                                     monkeypatch):
    """The SPMD rule under a real four-chip mesh whose sized axes are not
    all batch/head axes: the kernel's shard_map must be manual over every
    axis (a Mosaic kernel cannot be auto-partitioned over fsdp)."""
    import paddle_tpu as pt
    from paddle_tpu.nn.functional import attention

    monkeypatch.setattr(attention, "_flash_backend_ok", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "mp"))
    x = jax.ShapeDtypeStruct((1, 2048, 32, 128), BF16,
                             sharding=NamedSharding(mesh,
                                                    P(None, None, "mp")))

    def loss(q, k, v):
        out = attention.scaled_dot_product_attention(q, k, v, is_causal=True)
        return jnp.sum(out.astype(F32))

    with pt.use_mesh(mesh):
        text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert _kernels(text, "flash_attention_fwd") >= 1
    assert _kernels(text, "flash_attention_bwd_dkv") == 1


def test_paged_attention_in_tp_shard_map_compiles(mosaic, topo):
    """The decode kernel as ServingEngine(tp=4) runs it: inside a
    shard_map over mp, on kvh/4 heads of a pool sharded on its head dim."""
    mesh = Mesh(np.array(topo.devices), ("mp",))
    slots, page, d, pages, table = 8, 16, 128, 512, 32

    def S(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    heads_spec = P(None, None, "mp", None)
    q = S((slots, 1, 32, d), BF16, heads_spec)
    pool = S((pages, page, 8, d), BF16, heads_spec)
    tables, lens = S((slots, table), I32, P()), S((slots,), I32, P())
    step = jax.shard_map(
        paged_attention.paged_attention_tpu, mesh=mesh,
        in_specs=(heads_spec, heads_spec, heads_spec, P(), P()),
        out_specs=heads_spec, check_vma=False)
    text = _compile(step, q, pool, pool, tables, lens)
    assert _kernels(text, paged_attention.KERNEL_NAME) == 1


@pytest.mark.parametrize("slots", [16, 64], ids=["mistral_16", "nemotron_64"])
def test_sampler_compiles_without_a_vocabulary_wide_gather(
        no_persistent_cache, one_chip, slots):
    """``engine._sample_rows`` at the two serving cells' sizes. Of
    ``argsort`` + ``take_along_axis`` the chip's compiler made the sorted
    probabilities a gather of one element an index, the ``kCustom
    f32[slots x V]`` fusion that took 7.4 and 21.4 ms a step (PERF.md,
    PR 31); the sort returns them now, and the one gather left picks one
    index a row."""
    from paddle_tpu.serving.engine import _sample_rows
    V = 32768

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = _compile(_sample_rows, S((slots, V), BF16), S((slots,), F32),
                    S((slots,), F32), S((slots,), jnp.bool_),
                    S((slots,), I32), S((slots,), I32))
    gathers = re.findall(r"= (\w+\[[\d,]*\])\S* gather\(", text)
    assert gathers == [f"s32[{slots}]"]
    assert f"f32[{slots * V}]" not in text
    assert len(re.findall(r" sort\(", text)) == 1
    assert not re.search(r" scatter\(", text)


@pytest.mark.parametrize("rows", [1, 64], ids=["decode", "mixed"])
def test_a_donated_pool_is_written_in_place(mosaic, monkeypatch, one_chip,
                                            rows):
    """The cache path of a step program over four layers at the pool
    shape of ``mistral_7b_serve`` (16 slots, tables ``[16, 129]``, pool
    ``bf16[2065,16,8,128]``), jitted with the pool donated as
    ``ServingEngine`` does: ``paged_attention_write_attend`` (one row a
    slot and the kernel; 64 rows, the XLA gather and the rollback
    scatter of ``_mixed_tail``). Undonated, the decode form made nine
    copies of a layer's whole pool and the mixed form four asynchronous
    ones (3.5 s of a 51 s window on the chip: PERF.md, PR 34); donated,
    every pool array is aliased to its result and none is copied."""
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.serving.kv_cache import KVCachePool
    monkeypatch.setattr(attention, "_flash_backend_ok", lambda: True)
    layers, slots, heads, kv_heads, d = 4, 16, 32, 8, 128
    pages, page, table = 2065, 16, 129

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(pools, q, k, v, tables, lens, active, n_live, m):
        cols = jnp.arange(rows)[None, :]
        pos = lens[:, None] + cols
        new = []
        for pair in pools:
            q, pair = attention.paged_attention_write_attend(
                q, k, v, pair, tables, lens, pos, active,
                n_live if rows > 1 else None)
            new.append(pair)
        if rows > 1:
            rej = (cols < n_live[:, None]) & (cols > m[:, None])
            at = jnp.take_along_axis(tables, pos // page, axis=1)
            at, off = jnp.where(rej, at, 0), jnp.where(rej, pos % page, 0)
            new = [tuple(KVCachePool._pos_zero(a, at, off) for a in pair)
                   for pair in new]
        return q, new

    pool = S((pages, page, kv_heads, d), BF16)
    kv = S((slots, rows, kv_heads, d), BF16)
    lane = S((slots,), I32)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        [(pool, pool)] * layers, S((slots, rows, heads, d), BF16), kv, kv,
        S((slots, table), I32), lane, S((slots,), jnp.bool_), lane,
        lane).compile()
    text = compiled.as_text()
    assert _kernels(text, paged_attention.KERNEL_NAME) == (
        layers if rows == 1 else 0)
    whole = re.escape(f"bf16[{pages},{page},{kv_heads},{d}]")
    assert not re.findall(
        rf"= \(?{whole}[^=]*? (?:copy|copy-start|slice-start)\(", text)
    assert (compiled.memory_analysis().alias_size_in_bytes
            == 2 * layers * pages * page * kv_heads * d * 2)


def _latent_kernels(text: str) -> dict:
    """How often a program's text calls the latent kernel under each
    of its two names."""
    return {"decode": _kernels(text, paged_attention.LATENT_KERNEL_NAME),
            "rows": _kernels(text, paged_attention.LATENT_ROWS_KERNEL_NAME)}


def _float_shapes(text: str, kinds: str = "f32|bf16") -> set:
    return {tuple(int(d) for d in m.split(","))
            for m in re.findall(rf"(?:{kinds})\[([\d,]+)\]", text)}


def test_the_latent_rows_kernel_compiles_at_the_cell_shapes(mosaic,
                                                            one_chip):
    """``paged_latent_attention_rows`` alone at the shapes of
    ``openpangu_ultra_moe_serve``'s mixed program (64 rows x 128 heads a
    slot, rows of 640, V the first 512, tables ``[32, 257]`` over
    ``bf16[8225,16,640]``): Mosaic takes the tile that
    ``latent_rows_tile`` picks, its heads-major blocks, its loops over
    the live blocks and its request for VMEM; the pool is an operand,
    never a copy."""
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    heads_major = S((128, 32, 64, 640), BF16)
    pool = S((8225, 16, 640), BF16)
    lane = S((32,), I32)
    assert paged_attention.latent_kernel_applicable((32, 64, 128, 640),
                                                    pool.shape, 512)
    assert paged_attention.latent_rows_tile(64, 128) == (64, 16)
    # the kernel reads its queries and writes its result heads-major,
    # as the per-head products on either side of it hold them: from
    # and to such arrays both transposes are free and nothing is copied
    compiled = jax.jit(
        lambda q, p, t, n, live: paged_attention.paged_latent_attention_tpu(
            q.transpose(1, 2, 0, 3), p, t, n, 512, 192 ** -0.5, live
        ).transpose(2, 0, 1, 3)
    ).lower(heads_major, pool, S((32, 257), I32), lane, lane).compile()
    text = compiled.as_text()
    assert _latent_kernels(text) == {"decode": 0, "rows": 1}
    assert not re.findall(
        r"= \(?bf16\[8225,16,640\][^=]*? (?:copy|copy-start|slice-start)\(",
        text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("rows", [1, 64], ids=["decode", "mixed"])
def test_a_latent_pool_is_written_in_place_and_stays_compressed(
        mosaic, monkeypatch, one_chip, rows):
    """The cache path of a step program over two latent attention
    layers at the shapes of ``openpangu_ultra_moe_serve`` (32 slots, 128
    heads, rows of 512 + 64 in a pool ``bf16[8225,16,640]``, tables
    ``[32, 257]``), the pool donated: ``paged_latent_write_attend`` with
    one row a slot takes the kernel under the name
    ``paged_latent_attention_decode``, with 64 rows under
    ``paged_latent_attention_rows``. Either way the pool is aliased to
    its result and never copied (a row of 576 values, not padded to
    whole lanes, gets a layout in which a page is not one piece of
    memory and the kernel's operand is a copy of the whole pool: PR 35),
    and no array at all has an axis of a slot's whole table of keys:
    the scores stay in VMEM and the keys come by block table."""
    from paddle_tpu.nn.functional import attention
    monkeypatch.setattr(attention, "_flash_backend_ok", lambda: True)
    layers, slots, heads, width, v = 2, 32, 128, 576, 512
    pages, page, table = 8225, 16, 257

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(pools, q, row, tables, lens, active, n_live):
        pos = lens[:, None] + jnp.arange(rows)[None, :]
        out, new = 0, []
        for entry in pools:
            o, entry = attention.paged_latent_write_attend(
                q, row, entry, tables, lens, pos, active,
                n_live if rows > 1 else None, v_width=v, scale=192 ** -0.5)
            out, new = out + o, new + [entry]
        return out, new

    pool = S((pages, page, 640), BF16)
    lane = S((slots,), I32)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        [(pool,)] * layers, S((slots, rows, heads, width), BF16),
        S((slots, rows, width), BF16), S((slots, table), I32), lane,
        S((slots,), jnp.bool_), lane).compile()
    text = compiled.as_text()
    assert _latent_kernels(text) == (
        {"decode": layers, "rows": 0} if rows == 1
        else {"decode": 0, "rows": layers})
    assert not re.findall(
        r"= \(?bf16\[8225,16,640\][^=]*? (?:copy|copy-start|slice-start)\(",
        text)
    assert (compiled.memory_analysis().alias_size_in_bytes
            == layers * pages * page * 640 * 2)
    assert not [s for s in _float_shapes(text) if table * page in s]


def test_the_engine_decode_program_holds_the_latent_kernel(
        mosaic, monkeypatch, one_chip):
    """``ServingEngine``'s own two step programs for a toy of the
    latent-attention family (a lane-wide latent and 16 heads, so that
    the kernel's shape gate passes), lowered for the described chip
    from the engine's ``_warm_args``: the decode program calls the
    kernel once a layer under the name ``paged_latent_attention_decode``
    and the mixed program once a layer under
    ``paged_latent_attention_rows``, neither calls the other's, both
    alias the donated pool, and the mixed program has no float32 array
    with an axis of a slot's whole table of keys (10 pages of 16: no
    other size of the toy is 160)."""
    from paddle_tpu.models.pangu_moe import (PanguMoEForCausalLM,
                                             pangu_moe_tiny)
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.serving import ServingEngine
    monkeypatch.setattr(attention, "_flash_backend_ok", lambda: True)
    model = PanguMoEForCausalLM(pangu_moe_tiny(
        kv_lora_rank=128, num_attention_heads=16, dtype="bfloat16"))
    model.eval()
    eng = ServingEngine(model, num_pages=64, page_size=16, max_slots=8,
                        max_pages_per_slot=10, prefill_chunk=16)
    layers = model.config.num_hidden_layers
    for name, step in (("decode", eng._decode_step),
                       ("mixed", eng._mixed_step)):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            eng._warm_args(name))
        compiled = step.lower(*args).compile()
        text = compiled.as_text()
        assert _latent_kernels(text) == (
            {"decode": layers, "rows": 0} if name == "decode"
            else {"decode": 0, "rows": layers})
        assert (compiled.memory_analysis().alias_size_in_bytes
                >= sum(a.nbytes for e in eng.pool.pools for a in e))
        if name == "mixed":
            keys = eng.max_pages_per_slot * eng.page_size
            assert not [s for s in _float_shapes(text, "f32") if keys in s]


def test_the_scrub_zeroes_pages_of_a_donated_pool_in_place(one_chip):
    """``KVCachePool.scrub``'s one program at the pool shape of
    ``mistral_7b_serve`` over four layers: every pool array is aliased
    to its result and none is copied (eager, each eviction copied all
    32 arrays and cost 76 ms of a serving window: PERF.md, PR 34)."""
    from paddle_tpu.serving import kv_cache
    layers, shape = 4, (2065, 16, 8, 128)
    pool = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((kv_cache._SCRUB_WIDTH,), I32,
                               sharding=one_chip)
    compiled = kv_cache._scrub_in_place.lower(
        [(pool, pool)] * layers, idx, False).compile()
    whole = re.escape("bf16[%d,%d,%d,%d]" % shape)
    assert not re.findall(
        rf"= \(?{whole}[^=]*? (?:copy|copy-start|slice-start)\(",
        compiled.as_text())
    assert (compiled.memory_analysis().alias_size_in_bytes
            == 2 * layers * 2 * int(np.prod(shape)))


def test_multi_query_pages_reach_the_kernel_without_a_copy(mosaic, one_chip):
    """``paged_attention_decode`` at the shapes of ``jamba2_3b_serve``: 20
    query heads on ONE K/V head, pool ``bf16[8257,16,1,128]``. Mosaic
    takes the 20-row query tile as it is. The compiler keeps a size-1
    head axis outside the tiles (a page is 16 x 128 in one piece, the
    pool unpadded), and a block whose last two dimensions are (1, 128)
    asked for another tiling: the operand was a copy of the whole pool
    (68 MB of temporaries a call) until the wrapper handed the pool over
    as ``[pages, 16, 128]``, a bitcast."""
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = S((8257, 16, 1, 128), BF16)
    q = S((64, 1, 20, 128), BF16)
    assert paged_attention.kernel_applicable(q.shape, pool.shape)
    compiled = jax.jit(paged_attention.paged_attention_tpu).lower(
        q, pool, pool, S((64, 129), I32), S((64,), I32)).compile()
    text = compiled.as_text()
    assert _kernels(text, paged_attention.KERNEL_NAME) == 1
    assert not re.findall(
        r"= \(?bf16\[8257,16,1,128\][^=]*? (?:copy|copy-start|slice-start)\(",
        text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_the_selective_scan_kernel_compiles_at_the_cell_shapes(mosaic,
                                                               one_chip):
    """``selective_scan_rows`` alone at the shapes of
    ``jamba2_3b_serve``'s mixed program (64 slots x 64 rows x 5120
    channels, 16 state indices): Mosaic takes its dynamic row loads, its
    loops over the live rows and its request for VMEM; the donated state
    is aliased to the result and nothing the size of ``[rows, channels,
    state]`` exists."""
    def S(shape, dt=F32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, k, d, n = 64, 64, 5120, 16
    assert selective_scan.kernel_applicable((b, k, d), (b, n, d))
    compiled = jax.jit(selective_scan.selective_scan_tpu,
                       donate_argnums=(6,)).lower(
        S((b, k, d)), S((b, k, d)), S((d, n)), S((b, k, n)), S((b, k, n)),
        S((d,)), S((b, n, d)), S((b,), I32)).compile()
    text = compiled.as_text()
    assert _kernels(text, selective_scan.KERNEL_NAME) == 1
    assert compiled.memory_analysis().alias_size_in_bytes == b * n * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not [s for s in _float_shapes(text) if len(s) == 4]


def test_the_engine_s_programs_for_a_jamba_hold_both_kernels(
        mosaic, monkeypatch, one_chip):
    """``ServingEngine``'s own two step programs for a toy of the Jamba
    family, lowered for the described chip from the engine's
    ``_warm_args``: the decode program calls ``paged_attention_decode``
    once an attention layer (2 query heads on ONE K/V head here, 20 in
    the cell: no fall back to the XLA gather goes unnoticed) and no scan
    kernel (its one-row recurrence is a fused XLA pass); the mixed
    program calls ``selective_scan_rows`` once a Mamba layer and holds
    no float array with both a channel and a state axis beside the rows;
    both alias the donated pages and state."""
    from paddle_tpu.models.jamba import JambaForCausalLM, jamba_tiny
    from paddle_tpu.nn.functional import attention, ssm
    from paddle_tpu.serving import ServingEngine
    monkeypatch.setattr(attention, "_flash_backend_ok", lambda: True)
    monkeypatch.setattr(ssm, "_kernel_backend_ok", lambda: True)
    cfg = jamba_tiny(dtype="bfloat16")
    model = JambaForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, num_pages=64, page_size=16, max_slots=8,
                        max_pages_per_slot=10, prefill_chunk=16)
    kinds = [cfg.is_attention(i) for i in range(cfg.num_hidden_layers)]
    d, n = cfg.mamba_d_inner, cfg.mamba_d_state
    for name, step in (("decode", eng._decode_step),
                       ("mixed", eng._mixed_step)):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            eng._warm_args(name))
        compiled = step.lower(*args).compile()
        text = compiled.as_text()
        assert _kernels(text, paged_attention.KERNEL_NAME) == (
            kinds.count(True) if name == "decode" else 0)
        assert _kernels(text, selective_scan.KERNEL_NAME) == (
            kinds.count(False) if name == "mixed" else 0)
        held = (sum(a.nbytes for e in eng.pool.pools for a in e)
                + sum(a.nbytes for e in eng.pool.state for a in e))
        assert compiled.memory_analysis().alias_size_in_bytes >= held
        if name == "mixed":
            assert not [s for s in _float_shapes(text, "f32")
                        if len(s) == 4 and d in s and n in s]
