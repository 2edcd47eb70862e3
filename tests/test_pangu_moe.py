"""openPangu-Ultra-MoE (multi-head latent attention, four norms a block,
gated routed experts beside a gated shared expert) through
``ServingEngine``: the program against the plain reference
(``benchmarks/reference/pangu_moe.py``) at a tiny size on the CPU, on
seeded weights, on logits rather than tokens; the latent page format in
the pool; the decode kernel against the XLA path.

Tolerances: the program and the reference are both float32 here (matmul
precision "highest" in the reference, the CPU's float32 in the program)
and differ in the ORDER of sums and in the FORM of attention: the
program's served path attends against the cached latent (absorbed), the
reference makes every head's keys and values (unabsorbed). 2e-4 absolute
on logits of magnitude 0.5 holds that with room (readings are some 1e-6);
float32 also keeps the top 4 of 16 router scores apart, which bfloat16's
rounding would swap on near ties.
"""

import json
import os
import re
import signal
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import weights as W                       # noqa: E402
from benchmarks.families import pangu_moe as fam          # noqa: E402
from benchmarks.reference import pangu_moe as ref         # noqa: E402
from paddle_tpu.distributed.moe import (HeldExpertsMoE,   # noqa: E402
                                        _held_assignments,
                                        moe_held_dense_compute, relu2)
from paddle_tpu.nn.functional import attention            # noqa: E402
from paddle_tpu.ops.pallas import paged_attention         # noqa: E402
from paddle_tpu.serving import ServingEngine              # noqa: E402
from paddle_tpu.serving.errors import (LatentCacheError,  # noqa: E402
                                       TPConfigError)
from paddle_tpu.serving.kv_cache import (HybridCache,     # noqa: E402
                                         KVCachePool)

SEED = 2 ** 31 + 9
TOL = 2e-4
TEST_TIMEOUT_S = 120      # each test; the suite's own limit is 1470 s


@pytest.fixture(autouse=True)
def _hard_timeout(request):
    def expired(signum, frame):
        raise TimeoutError(f"{request.node.nodeid} exceeded its "
                           f"{TEST_TIMEOUT_S}s limit")
    old = signal.signal(signal.SIGALRM, expired)
    signal.alarm(TEST_TIMEOUT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                           "tiny_pangu_moe_serve_f32.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(cfg):
    m = fam.build_model(cfg, W.make_weights(
        SEED, fam.param_shapes(cfg), jnp.float32))
    m.eval()
    return m


def engine(model, **kw):
    # a float32 pool for the float32 model (the engine's default pool is
    # bfloat16 whatever the model)
    args = dict(num_pages=64, page_size=16, max_slots=4,
                max_pages_per_slot=16, prefill_chunk=16,
                kv_dtype=jnp.float32)
    args.update(kw)
    return ServingEngine(model, **args)


def prompt(n, salt=0, vocab=256):
    return np.random.default_rng([7, salt]).integers(0, vocab, n).tolist()


class Slots:
    """The call the step programs make, by hand: rows of token ids into
    chosen slots of an engine's pool, the logits back."""

    def __init__(self, model, eng):
        self.model, self.pool = model, eng.pool
        self.S, self.M = eng.max_slots, eng.max_pages_per_slot
        self.tables = np.zeros((self.S, self.M), np.int32)
        self.lens = np.zeros((self.S,), np.int32)
        for s in range(self.S):       # a slot's pages, once and for all
            self.tables[s, :9] = self.pool.alloc(9)

    def run(self, rows: dict, width=None):
        K = width or max(len(t) for t in rows.values())
        toks = np.zeros((self.S, K), np.int32)
        active = np.zeros((self.S,), bool)
        n_live = np.zeros((self.S,), np.int32)
        for s, t in rows.items():
            toks[s, :len(t)] = t
            active[s], n_live[s] = True, len(t)
        logits, cache = self.model(
            jnp.asarray(toks), None, HybridCache(self.pool.pools, []), 0,
            (jnp.asarray(self.tables), jnp.asarray(self.lens),
             jnp.asarray(active), jnp.asarray(n_live)))
        self.pool.pools = cache.kv
        self.counts = np.asarray(cache.counts)
        out = {s: np.asarray(logits[s, :len(t)]) for s, t in rows.items()}
        for s, t in rows.items():
            self.lens[s] += len(t)
        return out


def test_cache_free_forward_matches_the_reference(cfg, model):
    ids = [prompt(48), prompt(31, 1)]
    want = ref.logits_rows(SEED, cfg, ids, [0, 0])
    for seq, w in zip(ids, want):
        got = np.asarray(model(jnp.asarray([seq], jnp.int32)))[0]
        assert np.abs(got - w).max() < TOL
        assert np.abs(w).max() > 0.3


def test_prefill_in_chunks_then_decode_matches_the_reference(cfg, model):
    """Two slots prefilled in chunks of unequal size (the absorbed form
    against the cache, rows of one pass attending each other through
    it), then decoded row by row: every row's logits are the reference's
    full forward."""
    seqs = {0: prompt(45, 2), 2: prompt(23, 3)}
    want = dict(zip(seqs, ref.logits_rows(SEED, cfg, list(seqs.values()),
                                          [0, 0])))
    sl = Slots(model, engine(model))
    got = {s: [] for s in seqs}
    for lo, hi in ((0, 16), (16, 23), (23, 39)):
        out = sl.run({s: t[lo:hi] for s, t in seqs.items() if t[lo:hi]},
                     width=16)
        for s, lg in out.items():
            got[s].append(lg)
    for i in range(39, 45):                         # slot 0 decodes alone
        got[0].append(sl.run({0: seqs[0][i:i + 1]})[0])
    for s in seqs:
        assert np.abs(np.concatenate(got[s]) - want[s]).max() < TOL, s
    assert sl.counts[0] == 1 * cfg["num_experts_per_tok"] * 2  # 2 layers


def test_absorbed_attention_is_the_unabsorbed_one(cfg, model):
    """One attention layer alone: the served path over a cache (queries
    carried through W_UK, the latent mix through W_UV) against the
    cache-free path (per-head keys and values from the latent)."""
    attn = model.model.layers[1].self_attn
    u = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 24, cfg["hidden_size"])), jnp.float32)
    want = np.asarray(attn(u))
    pool = KVCachePool(1, 8, 16, 0, 0, jnp.float32,
                       latent_width=model.config.latent_row_width)
    tables = jnp.asarray([[1, 2, 0], [3, 4, 0]], jnp.int32)
    got, entry = attn(u, pool.pools[0],
                      (tables, jnp.zeros((2,), jnp.int32),
                       jnp.ones((2,), bool)))
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    rows = np.asarray(entry[0])
    width = model.config.latent_row_width
    assert rows.shape == (8, 16, 128) and not rows[..., width:].any()
    assert not rows[0].any() and rows[1].any() and rows[4, :8].any()
    assert not rows[4, 8:].any()          # 24 rows: a page and a half


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_decode_kernel_is_the_xla_path_at_ragged_lengths(dtype):
    """``paged_latent_attention_tpu`` in interpret mode against the
    gather-and-attend path: lengths of 1 row, a page's last row, a
    page's first, one past a group of pages, and a full table."""
    rng = np.random.default_rng(5)
    b, h, w, vw, ps, M = 5, 16, 256, 128, 16, 19
    pool = jnp.asarray(rng.standard_normal((b * M + 1, ps, w)), dtype)
    q = jnp.asarray(rng.standard_normal((b, 1, h, w)), dtype)
    tables = jnp.asarray(1 + rng.permutation(b * M).reshape(b, M), jnp.int32)
    lens = jnp.asarray([0, 15, 16, 128, M * ps - 1], jnp.int32)
    assert paged_attention.latent_kernel_applicable(q.shape, pool.shape, vw)
    got = paged_attention.paged_latent_attention_tpu(q, pool, tables, lens,
                                                     vw, 0.07)
    want = attention._latent_attend(
        q, pool[tables].reshape(b, -1, w), lens, vw, 0.07)
    assert got.shape == (b, 1, h, vw) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want)).max() < tol
    assert paged_attention.latent_kernel_applicable(
        (b, 16, h, w), pool.shape, vw)              # more rows a slot too
    assert not paged_attention.latent_kernel_applicable(
        (b, 2, h, w), pool.shape, vw)   # in blocks that fill a bf16 tile
    assert not paged_attention.latent_kernel_applicable(
        (b, 1, h, 192), (8, ps, 192), vw)           # rows fill the lanes


def _rows_case(t, dtype):
    """Lanes of the latent kernel with ``t`` rows a slot: lengths that
    start a page, end one, cross a page and a group of 8 pages inside
    the slot's rows, a full table; live rows from 0 (an inactive slot)
    to ``t``; the table entries past a slot's live pages point at page
    0, which holds NaN: a step that read one would show."""
    rng = np.random.default_rng(5)
    b, h, w, vw, ps, M = 7, 16, 256, 128, 16, 19
    pool = jnp.asarray(rng.standard_normal((b * M + 1, ps, w)), dtype)
    q = jnp.asarray(rng.standard_normal((b, t, h, w)), dtype)
    lens = np.array([0, 15, 16, 8 * ps - (t + 1) // 2, M * ps - t, 77, 40],
                    np.int32)
    live = np.array([t, max(t - 1, 1), 1, t, t, 0, (t + 1) // 2], np.int32)
    tables = 1 + rng.permutation(b * M).reshape(b, M).astype(np.int32)
    for s in range(b):
        tables[s, (lens[s] + max(live[s], 1) - 1) // ps + 1:] = 0
    return q, pool.at[0].set(jnp.nan), tables, lens, live, vw


@pytest.fixture(params=[(8192, 2048), (256, 64)], ids=["whole", "cut"])
def tiling(request, monkeypatch):
    """The kernel's tile and block of rows at the toy's 16 heads: whole
    (one tile, one block) and cut small (tiles of 16 rows, blocks of 4)."""
    monkeypatch.setattr(paged_attention, "_LATENT_TILE_ROWS",
                        request.param[0])
    monkeypatch.setattr(paged_attention, "_LATENT_SUB_ROWS",
                        request.param[1])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t", [1, 3, 16, 64])
def test_the_rows_kernel_is_the_xla_path_on_live_rows_and_zero_on_dead(
        t, dtype, tiling):
    """``paged_latent_attention_tpu`` with ``t`` query rows a slot, in
    interpret mode, against ``_latent_attend`` over the gathered table:
    live rows agree within the decode kernel's tolerance, rows ``>=
    n_live`` and every row of an inactive slot are exactly zero, and
    nothing is NaN although every page that is not live is."""
    q, pool, tables, lens, live, vw = _rows_case(t, dtype)
    b, _, h, w = q.shape
    got = paged_attention.paged_latent_attention_tpu(
        q, pool, jnp.asarray(tables), jnp.asarray(lens), vw, 0.07,
        jnp.asarray(live))
    want = attention._latent_attend(
        q, pool.at[0].set(0)[tables].reshape(b, -1, w), jnp.asarray(lens),
        vw, 0.07)
    assert got.shape == (b, t, h, vw) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    rows = np.arange(t)[None, :] < live[:, None]
    assert not np.isnan(got).any()
    assert not got[~rows].any()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert np.abs(got[rows] - np.asarray(want)[rows]).max() < tol


@pytest.mark.parametrize("t", [1, 3, 16, 64])
def test_the_count_of_grid_steps_is_the_kernels_own(monkeypatch, t, tiling):
    """``latent_grid_steps`` (what the engine bumps as
    ``latent_steps_live`` / ``latent_steps_dispatched``) against the
    kernel itself: every grid step of an interpreted call reports what
    its ``pl.when`` was handed; and against the definition, counted row
    by row: a step is live when some live row of its tile attends some
    position of its page group."""
    q, pool, tables, lens, live, vw = _rows_case(t, jnp.float32)
    h, (ps, M) = q.shape[2], (pool.shape[1], tables.shape[1])
    counted = paged_attention.latent_grid_steps(
        lens, live, rows=t, heads=h, max_pages=M, page_size=ps)
    tile, _ = paged_attention.latent_rows_tile(t, h)
    span = paged_attention._LATENT_PAGES * ps
    by_rows = sum(
        any(i * tile <= j < min((i + 1) * tile, n) and g * span <= L + j
            for j in range(t))
        for L, n in zip(lens, live) for i in range(t // tile)
        for g in range(-(-M // paged_attention._LATENT_PAGES)))
    assert counted == (by_rows, len(lens) * (t // tile) * -(-M // 8))
    seen, predicate = [], paged_attention.latent_step_live

    def reporting(last_pos, g, span):
        computes = predicate(last_pos, g, span)
        if isinstance(computes, jax.Array):     # inside the kernel
            jax.debug.callback(lambda c: seen.append(bool(c)), computes)
        return computes

    monkeypatch.setattr(paged_attention, "latent_step_live", reporting)
    jax.block_until_ready(paged_attention.paged_latent_attention_tpu(
        q, pool, jnp.asarray(tables), jnp.asarray(lens), vw, 0.07,
        jnp.asarray(live)))
    jax.effects_barrier()
    assert (sum(seen), len(seen)) == counted


def test_the_xla_path_never_holds_all_heads_rows_and_keys(monkeypatch):
    """The mixed program's attention walks the heads in blocks: with
    room for two heads' scores, no array of the compiled text has all
    of slots, rows, heads and keys."""
    b, t, h, w, S, vw = 2, 8, 8, 128, 64, 32
    monkeypatch.setattr(attention, "_LATENT_SCORE_BYTES", 4 * b * t * S * 2)
    q = jnp.ones((b, t, h, w), jnp.float32)
    rows = jnp.ones((b, S, w), jnp.float32)
    lens = jnp.zeros((b,), jnp.int32)
    text = jax.jit(attention._latent_attend, static_argnums=(3, 4)).lower(
        q, rows, lens, vw, 0.1).compile().as_text()
    shapes = {tuple(int(d) for d in m.split(","))
              for m in re.findall(r"f32\[([\d,]+)\]", text)}
    assert (b, t, 2, S) in shapes
    assert not [s for s in shapes
                if sorted(s) == sorted((b, t, h, S))]
    whole = attention._latent_attend(q, rows, lens, vw, 0.1)
    monkeypatch.setattr(attention, "_LATENT_SCORE_BYTES", 1 << 29)
    assert np.array_equal(np.asarray(whole), np.asarray(
        attention._latent_attend(q, rows, lens, vw, 0.1)))


def test_step_programs_keep_the_cache_compressed(model):
    """``lower_step_programs()``: neither program's compiled text holds
    an array of per-head keys or values over a slot's keys (heads x
    (nope + rope) or heads x v wide, over ``max_pages_per_slot`` pages of
    keys); the donated latent pool is aliased to its result."""
    eng = engine(model)
    c = model.config
    S, keys, h = eng.max_slots, eng.max_pages_per_slot * eng.page_size, \
        c.num_attention_heads
    for name, lowered in eng.lower_step_programs().items():
        compiled = lowered.compile()
        shapes = {tuple(int(d) for d in m.split(","))
                  for m in re.findall(r"f32\[([\d,]+)\]",
                                      compiled.as_text())}
        per_head = [s for s in shapes if len(s) >= 4 and S in s
                    and keys in s and h in s
                    and s[-1] in (c.qk_nope_head_dim + c.qk_rope_head_dim,
                                  c.qk_nope_head_dim, c.v_head_dim)]
        assert not per_head, (name, per_head)
        assert (compiled.memory_analysis().alias_size_in_bytes
                >= sum(a.nbytes for e in eng.pool.pools for a in e))


def test_engine_serves_chunked_prefill_and_decode(cfg, model):
    """Through ``ServingEngine``: three requests, prompts over several
    chunks, greedy; every served token is the reference's first choice
    at its position (float32 on both sides)."""
    eng = engine(model)
    prompts = [prompt(5, 4), prompt(37, 5), prompt(70, 6)]
    rids = [eng.add_request(p, 10) for p in prompts]
    out = {r: [] for r in rids}
    for _ in range(100):
        for ev in eng.step():
            if ev["token"] is not None:
                out[ev["rid"]].append(int(ev["token"]))
        if not eng.scheduler.running and not eng.scheduler.queue_depth:
            break
    seqs = [p + out[r][:-1] for p, r in zip(prompts, rids)]
    want = ref.logits_rows(SEED, cfg, seqs, [len(p) - 1 for p in prompts])
    for r, w in zip(rids, want):
        assert len(out[r]) == 10
        best = w.max(axis=-1)
        picked = w[np.arange(10), out[r]]
        assert (best - picked).max() < TOL
    assert eng.step_program_counts() == {"decode": 1, "mixed": 1}
    assert eng.stats()["latent_cache"] and eng.stats()["prefix_cache"]
    eng.audit_pool()


def test_a_traced_engine_counts_the_rows_kernels_grid_steps(model):
    """Every traced mixed dispatch bumps ``latent_steps_dispatched`` by
    the grid of one call of the rows kernel over the engine's lanes
    (every slot, idle or not) and ``latent_steps_live`` by the steps of
    it that compute: a slot with a chunk of a short prompt has one live
    page group of two, a slot that sits out has none."""
    from paddle_tpu.observability.trace import Tracer
    tr = Tracer()
    eng = engine(model, tracer=tr)
    eng.add_request(prompt(37, 5), 4)
    eng.add_request(prompt(5, 4), 4)
    while eng.scheduler.running or eng.scheduler.queue_depth:
        eng.step()
    c = tr.counters
    tile, _ = paged_attention.latent_rows_tile(
        16, model.config.num_attention_heads)
    groups = -(-eng.max_pages_per_slot // paged_attention._LATENT_PAGES)
    assert c["latent_steps_dispatched"] == (
        c["mixed_steps"] * eng.max_slots * (16 // tile) * groups)
    # 37 + 5 prompt tokens in chunks of 16, then the decode lanes that
    # share a mixed step with a chunk: at least a live step a chunk, and
    # never more than the two busy slots' single live group each
    assert c["chunks"] <= c["latent_steps_live"] <= 2 * c["mixed_steps"]


def test_prefix_cache_over_latent_pages(model):
    """The prefix cache stays on: a second request with the same first
    40 tokens maps the first's two full pages, and each is served the
    tokens it gets without the cache."""
    def serve(eng, p, n=6):
        rid, toks = eng.add_request(p, n), []
        for _ in range(60):
            for ev in eng.step():
                if ev["token"] is not None:
                    toks.append(int(ev["token"]))
            if not eng.scheduler.running:
                break
        return toks

    shared = prompt(40, 7)
    a, b = shared + prompt(9, 8), shared + prompt(13, 9)
    cold = engine(model, prefix_cache=False)
    want = [serve(cold, a), serve(cold, b)]
    eng = engine(model)
    assert [serve(eng, a), serve(eng, b)] == want
    assert eng.pool.counters["prefix_hit_pages"] == 2
    assert serve(eng, a) == want[0]                 # its own pages again
    assert eng.pool.counters["prefix_hit_pages"] >= 5
    eng.audit_pool()


def test_a_latent_page_is_scrubbed_copied_and_freed():
    pool = KVCachePool(3, 8, 16, 0, 0, jnp.float32, latent_width=48)
    assert [tuple(a.shape for a in e) for e in pool.pools] == \
        [((8, 16, 128),)] * 3
    assert pool.kv_bytes_per_token() == 3 * 128 * 4
    src, dst = pool.alloc(2)
    pool.pools = [(e[0].at[src].set(li + 1.0),)
                  for li, e in enumerate(pool.pools)]
    pool.cow_into(src, dst)
    for li, (rows,) in enumerate(pool.pools):
        assert float(rows[dst].min()) == li + 1.0
    pool.warm_scrub()
    pool.scrub([src])
    assert not any(float(jnp.abs(rows[src]).max()) for rows, in pool.pools)
    assert all(float(rows[dst].min()) > 0 for rows, in pool.pools)
    pool.free([src])
    pool.rewind([dst], 3, 9)
    assert not float(jnp.abs(pool.pools[1][0][dst, 3:9]).max())
    assert float(pool.pools[1][0][dst, 9:].min()) == 2.0
    assert pool.audit(block_tables=[[dst]])["held"] == 1
    pool.pools[2] = (pool.pools[2][0].at[src, 0, 0].set(1.0),)
    with pytest.raises(AssertionError, match="latent content in layer 2"):
        pool.audit(block_tables=[[dst]])


def test_what_has_not_been_carried_over_is_refused_by_name(model):
    for kw in ({"speculative": 3}, {"host_tier": True}, {"lora": True},
               {"kv_dtype": "int8"}, {"kv_quant": True, "kv_dtype": None},
               {"snapshot_store": object()}):
        with pytest.raises(LatentCacheError, match="latent cache"):
            engine(model, **kw)
    for kw in ({"tp": 2}, {"pp": 2}):
        with pytest.raises(TPConfigError, match="PanguMoEConfig"):
            engine(model, **kw)
    eng = engine(model)
    with pytest.raises(LatentCacheError):
        eng.add_request(prompt(8), 4, prefill_only=True)
    with pytest.raises(LatentCacheError):
        eng.save_snapshot("/nonexistent/never-written")
    with pytest.raises(LatentCacheError):
        eng.restore("/nonexistent/never-read")
    with pytest.raises(LatentCacheError):
        eng.restore_request(None)
    for kw in ({"quantized": True}, {"host_tier": True}, {"tp_degree": 2},
               {"pp_degree": 2}):
        with pytest.raises(LatentCacheError):
            KVCachePool(2, 8, 16, 0, 0, latent_width=48, **kw)
    assert not LatentCacheError.retryable
    assert isinstance(LatentCacheError("x"), ValueError)


def _moe_weights(cfg):
    return ref.layer_weights(SEED, cfg, 1)


def test_the_four_shares_sum_to_the_uncut_layer(cfg):
    """The guide's share test: the routed parts of the four chips that
    share a layer (experts 0-3 ... 12-15 of 16 here, as 0-15 ... 240-255
    of 256 in the cell), summed, plus the shared expert once, are the
    reference's UNCUT layer."""
    n = cfg["n_routed_experts"]
    whole = dict(cfg, experts_held=[0, n])
    lw = _moe_weights(whole)
    u = jnp.asarray(np.random.default_rng(14).standard_normal(
        (3, 20, cfg["hidden_size"])), jnp.float32)
    want = np.asarray(ref.moe(u, lw, whole))
    shared = np.asarray(
        (jax.nn.silu(u @ lw["mlp.shared_gate.weight"])
         * (u @ lw["mlp.shared_up.weight"])) @ lw["mlp.shared_down.weight"])
    total, held = -3 * shared, 0
    for first in range(0, n, 4):
        layer = HeldExpertsMoE(
            cfg["hidden_size"], None, cfg["moe_intermediate_size"], n,
            cfg["num_experts_per_tok"], experts_held=(first, 4),
            d_shared=cfg["moe_intermediate_size"], activation=jax.nn.silu,
            gated=True, shared_gated=True, score_bias=False,
            routed_scaling_factor=cfg["routed_scaling_factor"])
        state = {k[len("mlp."):]: v for k, v in lw.items()
                 if k.startswith("mlp.")}
        for k in ("experts.w_gate", "experts.w_in", "experts.w_out"):
            state[k] = state[k][first:first + 4]
        missing, unexpected = layer.set_state_dict(state)
        assert not missing and not unexpected
        out, counts = layer(u)
        total = total + np.asarray(out)
        held += int(counts[1])
        assert int(counts[0]) == 60 * cfg["num_experts_per_tok"]
    assert held == 60 * cfg["num_experts_per_tok"]   # no row dropped
    assert np.abs(total - want).max() < TOL
    assert np.abs(want - shared).max() > 10 * TOL    # the experts matter


def test_a_latent_width_builds_the_layer_as_before_bit_for_bit():
    """``HeldExpertsMoE`` with a latent width, no gates and the score
    bias (what ``nemotron3_super_serve`` builds): the leaves it had, and
    its result equal in every bit to the layer's former body written
    out: router, ``fc1_latent_proj``, the held experts, ``fc2_latent_
    proj``, plus the ungated shared expert."""
    layer = HeldExpertsMoE(64, 32, 48, 8, 3, experts_held=(2, 4),
                           d_shared=96, routed_scaling_factor=5.0)
    assert sorted(layer.state_dict()) == [
        "e_score_correction_bias", "experts.w_in", "experts.w_out",
        "fc1_latent_proj.weight", "fc2_latent_proj.weight", "gate.weight",
        "shared_down.weight", "shared_up.weight"]
    rng = np.random.default_rng(16)
    layer.set_state_dict({k: jnp.asarray(
        0.2 * rng.standard_normal(v.shape), v.dtype)
        for k, v in layer.state_dict().items()})
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    live = jnp.arange(40) % 5 != 0
    got, counts = layer(x, live)
    idx, w = layer.route(x)
    local, held = _held_assignments(idx, live, 2, 4)
    routed = moe_held_dense_compute(
        layer.fc1_latent_proj(x), local, w, layer.experts.w_in, None,
        layer.experts.w_out, relu2)
    want = (layer.fc2_latent_proj(routed.astype(x.dtype))
            + layer.shared_down(relu2(layer.shared_up(x))))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(counts[0]) == 32 * 3 and int(counts[1]) == int(held.sum())
