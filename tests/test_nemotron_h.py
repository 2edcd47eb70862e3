"""Nemotron-H (Mamba-2 / attention / LatentMoE blocks by a pattern string)
through ``ServingEngine``: the program against the plain reference
(``benchmarks/reference/nemotron_h.py``) at a tiny size on the CPU, on
seeded weights, on logits and states rather than tokens, and the state
semantics a recurrent model needs from the engine.

Tolerances: the program and the reference are both float32 here (matmul
precision "highest" in the reference, the CPU's float32 in the program)
and differ in the ORDER of sums: the program's chunked scan against the
reference's row-by-row recurrence, paged attention against full
attention. 2e-4 absolute on logits of magnitude 0.5 holds that with
room (readings are some 1e-6); where two runs of the SAME program are
compared the test asks for equal bits.
"""

import json
import logging
import os
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
from benchmarks.families import nemotron_h as fam         # noqa: E402
from benchmarks.reference import nemotron_h as ref        # noqa: E402
from paddle_tpu.distributed.moe import HeldExpertsMoE     # noqa: E402
from paddle_tpu.nn import functional as F                 # noqa: E402
from paddle_tpu.serving import ServingEngine              # noqa: E402
from paddle_tpu.serving.errors import (RecurrentStateError,  # noqa: E402
                                       TPConfigError)
from paddle_tpu.serving.kv_cache import HybridCache       # noqa: E402

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


def tiny_cfg():
    with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                           "tiny_nemotron_h_serve_f32.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


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
        self.ps = eng.page_size
        self.tables = np.zeros((self.S, self.M), np.int32)
        self.lens = np.zeros((self.S,), np.int32)
        for s in range(self.S):       # a slot's pages, once and for all
            self.tables[s, :9] = self.pool.alloc(9)

    def run(self, rows: dict, width=None):
        """rows: slot -> token ids appended to that slot. Returns
        slot -> logits [n, vocab] of its rows."""
        K = width or max(len(t) for t in rows.values())
        toks = np.zeros((self.S, K), np.int32)
        active = np.zeros((self.S,), bool)
        n_live = np.zeros((self.S,), np.int32)
        for s, t in rows.items():
            toks[s, :len(t)] = t
            active[s], n_live[s] = True, len(t)
        cache = HybridCache(self.pool.pools, self.pool.state)
        logits, cache = self.model(
            jnp.asarray(toks), None, cache, 0,
            (jnp.asarray(self.tables), jnp.asarray(self.lens),
             jnp.asarray(active), jnp.asarray(n_live)))
        self.pool.pools, self.pool.state = cache.kv, cache.state
        self.counts = np.asarray(cache.counts)
        out = {s: np.asarray(logits[s, :len(t)]) for s, t in rows.items()}
        for s, t in rows.items():
            self.lens[s] += len(t)
        return out

    def restart(self, slot):
        self.lens[slot] = 0


def test_cache_free_forward_matches_the_reference(cfg, model):
    ids = [prompt(48, 1), prompt(48, 2)]
    got = np.asarray(model(jnp.asarray(ids, jnp.int32)))
    want = ref.logits_rows(SEED, cfg, ids, [0, 0])
    for g, w in zip(got, want):
        assert np.abs(g - w).max() < TOL
    assert np.abs(want[0]).max() > 0.1          # not a comparison of noughts


def test_bf16_weights_and_reference_agree_leaf_for_leaf():
    cfg = dict(tiny_cfg(), torch_dtype="bfloat16")
    m = fam.build_model(cfg, W.make_weights(
        SEED, fam.param_shapes(cfg), jnp.bfloat16))
    sd = m.state_dict()
    for k, shp in fam.param_shapes(cfg).items():
        assert np.array_equal(np.asarray(sd[k], np.float32),
                              np.asarray(ref._leaf(SEED, k, shp, cfg))), k
    a = -np.exp(np.asarray(sd["model.layers.0.mixer.A_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(sd["model.layers.0.mixer.dt_bias"],
                                    np.float32)))
    assert ((a <= -1) & (a >= -16)).all()
    assert ((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)).all()
    assert ((np.exp(dt * a) > 0.2) & (np.exp(dt * a) < 0.9991)).all()


def test_prefill_then_decode_through_the_pools_matches_the_reference(
        cfg, model):
    """Chunks of 16, 16 and 5 prompt rows, then 6 one-row decode steps
    (the recurrence's other form), against the reference's one pass."""
    slots = Slots(model, engine(model))
    seq = prompt(43, 3)
    got = [slots.run({1: seq[:16]})[1], slots.run({1: seq[16:32]})[1],
           slots.run({1: seq[32:37]})[1]]
    got += [slots.run({1: [t]})[1] for t in seq[37:]]
    got = np.concatenate(got)
    want = ref.logits_rows(SEED, cfg, [seq], [0])[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_chunking_64_64_7_or_one_pass_gives_the_same_logits_and_state(model):
    seq = prompt(135, 4)
    one, three = Slots(model, engine(model)), Slots(model, engine(model))
    a = one.run({0: seq})[0]
    b = np.concatenate([three.run({0: seq[:64]})[0],
                        three.run({0: seq[64:128]})[0],
                        three.run({0: seq[128:]}, width=64)[0]])
    assert np.abs(a - b).max() < TOL
    for sa, sb in zip(one.pool.state, three.pool.state):
        for x, y in zip(sa, sb):        # conv window, SSM state of slot 0
            assert np.abs(np.asarray(x[0]) - np.asarray(y[0])).max() < TOL


def test_a_mixed_step_advances_a_slot_by_its_live_rows_and_no_more(model):
    """Slot 0 runs 5 live rows in a pass 16 wide beside slot 2's 16: its
    state is what 5 rows alone leave, to the bit; slot 1, inactive,
    keeps what it had, to the bit."""
    a, b = Slots(model, engine(model)), Slots(model, engine(model))
    first, more, other = prompt(9, 5), prompt(5, 6), prompt(16, 7)
    for s in (a, b):
        s.run({0: first, 1: first})
    kept = [[np.asarray(x[1]) for x in layer] for layer in a.pool.state]
    a.run({0: more, 2: other})          # 16 wide, 5 live in slot 0
    b.run({0: more})                    # 5 wide
    for la, lb, lk in zip(a.pool.state, b.pool.state, kept):
        for x, y, k in zip(la, lb, lk):
            assert np.abs(np.asarray(x[0]) - np.asarray(y[0])).max() < 1e-6
            assert np.array_equal(np.asarray(x[1]), k)
    # a decode step: an inactive slot's state stays, to the bit
    a.run({0: [3]})
    for la, lk in zip(a.pool.state, kept):
        for x, k in zip(la, lk):
            assert np.array_equal(np.asarray(x[1]), k)


def test_a_reused_slot_starts_from_zero_state(model):
    used, fresh = Slots(model, engine(model)), Slots(model, engine(model))
    used.run({2: prompt(40, 8)})
    assert float(jnp.abs(used.pool.state[0][1][2]).max()) > 0
    used.restart(2)                     # the next tenant: position 0
    seq = prompt(21, 9)
    a = np.concatenate([used.run({2: seq[:16]})[2], used.run({2: seq[16:]})[2]])
    b = np.concatenate([fresh.run({2: seq[:16]})[2],
                        fresh.run({2: seq[16:]})[2]])
    assert np.array_equal(a, b)


def test_engine_reuses_a_slot_and_recomputes_after_preemption(model):
    """Tokens, because the engine hands out nothing else: the same
    compiled programs on the same rows give the same bits, so a request
    served after another finished in its slot, and one preempted and
    recomputed, emit what they emit alone."""
    reqs = [(prompt(37, 10), 9), (prompt(20, 11), 30), (prompt(28, 12), 12)]
    alone = []
    for p, n in reqs:
        eng = engine(model)
        rid = eng.add_request(p, n)
        alone.append(eng.run_to_completion()[rid])
    # one slot: each request runs in the slot the last one left
    eng = engine(model, max_slots=1)
    rids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run_to_completion()
    assert [out[r] for r in rids] == alone
    assert eng.audit_pool()["state_slots"] == 0
    # a pool too small for all three: the youngest is preempted and
    # recomputed from position 0 (with zero state)
    eng = engine(model, num_pages=8, max_slots=3)
    rids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run_to_completion()
    assert eng.stats()["preemptions"] > 0
    assert [out[r] for r in rids] == alone
    assert eng.step_program_counts() == {"decode": 1, "mixed": 1}


def test_pool_counts_the_state_and_audits_it(cfg, model):
    eng = engine(model)
    per_slot = 2 * ((3 * (4 * 16 + 2 * 2 * 16)) * 4 + 4 * 16 * 16 * 4)
    st = eng.stats()["pool"]
    assert st["state_layers"] == 2 and st["state_bytes_per_slot"] == per_slot
    assert len(eng.pool.pools) == 2          # K/V only for the 2 of 6
    eng.add_request(prompt(20, 13), 4)
    eng.step()
    st = eng.stats()["pool"]
    assert st["state_slots_live"] == 1 and st["state_bytes_live"] == per_slot
    assert eng.audit_pool()["state_slots"] == 1
    eng.pool.state_slots[3] = "nobody"       # a leaked row is caught
    with pytest.raises(AssertionError, match="state rows"):
        eng.audit_pool()
    del eng.pool.state_slots[3]
    eng.run_to_completion()
    assert eng.stats()["pool"]["state_slots_live"] == 0
    jax.block_until_ready(eng.pool.pools)
    jax.block_until_ready(eng.pool.state)


def moe_weights(cfg):
    """Layer 1's leaves (an E layer). The toy configuration makes its
    routed path LOUDER (``assumed.leaf_gains``, in the program and the
    reference alike): at std 0.02 and a latent width of 32 the experts
    would add 1e-4 to an output of 1e-2, and a comparison would pass
    without them."""
    return ref.layer_weights(SEED, cfg, 1)


def test_the_four_shares_sum_to_the_uncut_layer(cfg):
    """The guide's share test: the routed parts of the four chips that
    share a layer (experts 0-1, 2-3, 4-5, 6-7 of 8 here, as 0-127 ...
    384-511 of 512 in the cell), summed, plus the shared expert once,
    are the reference's UNCUT layer."""
    whole = dict(cfg, experts_held=[0, cfg["n_routed_experts"]])
    lw = moe_weights(whole)
    u = jnp.asarray(np.random.default_rng(14).standard_normal(
        (3, 20, cfg["hidden_size"])), jnp.float32)
    want = np.asarray(ref.moe(u, lw, whole))
    shared = np.asarray(ref.relu2(u @ lw["mixer.shared_up.weight"])
                        @ lw["mixer.shared_down.weight"])
    total, held = -3 * shared, 0
    for first in range(0, 8, 2):
        layer = HeldExpertsMoE(
            cfg["hidden_size"], cfg["moe_latent_size"],
            cfg["moe_intermediate_size"], 8, cfg["num_experts_per_tok"],
            experts_held=(first, 2),
            d_shared=cfg["moe_shared_expert_intermediate_size"],
            routed_scaling_factor=cfg["routed_scaling_factor"])
        state = {k[len("mixer."):]: v for k, v in lw.items()
                 if k.startswith("mixer.")}
        state["experts.w_in"] = state["experts.w_in"][first:first + 2]
        state["experts.w_out"] = state["experts.w_out"][first:first + 2]
        missing, unexpected = layer.set_state_dict(state)
        assert not missing and not unexpected
        out, counts = layer(u)
        total = total + np.asarray(out)
        held += int(counts[1])
        assert int(counts[0]) == 60 * cfg["num_experts_per_tok"]
    assert held == 60 * cfg["num_experts_per_tok"]   # no row dropped
    assert np.abs(total - want).max() < TOL
    assert np.abs(want - shared).max() > 10 * TOL    # the experts matter


def test_many_rows_take_the_sorted_tiles_and_agree_with_the_reference(cfg):
    """Over ``DENSE_ROWS`` rows the held experts' part is computed tile
    by tile over sorted rows; dead rows get the shared expert only."""
    lw = moe_weights(cfg)
    layer = HeldExpertsMoE(
        cfg["hidden_size"], cfg["moe_latent_size"],
        cfg["moe_intermediate_size"], 8, cfg["num_experts_per_tok"],
        experts_held=tuple(cfg["experts_held"]),
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"], tile_rows=8)
    layer.set_state_dict({k[len("mixer."):]: v for k, v in lw.items()
                          if k.startswith("mixer.")})
    rows = HeldExpertsMoE.DENSE_ROWS + 44
    u = jnp.asarray(np.random.default_rng(15).standard_normal(
        (1, rows, cfg["hidden_size"])), jnp.float32)
    want = np.asarray(ref.moe(u, lw, cfg))
    out, counts = layer(u)
    assert np.abs(np.asarray(out) - want).max() < TOL
    few, _ = layer(u[:, :40])                        # the dense way
    assert np.abs(np.asarray(few) - want[:, :40]).max() < TOL
    live = jnp.arange(rows) % 3 != 0
    part, c2 = layer(u, live)
    shared = np.asarray(ref.relu2(u @ lw["mixer.shared_up.weight"])
                        @ lw["mixer.shared_down.weight"])
    got = np.asarray(part)
    assert np.abs(got[0, ::3] - shared[0, ::3]).max() < 1e-6
    assert np.abs(got[0, 1::3] - want[0, 1::3]).max() < TOL
    assert int(c2[0]) == int(live.sum()) * cfg["num_experts_per_tok"]
    assert 0 < int(c2[1]) < int(counts[1]) and int(c2[2]) == 4


def test_scan_forms_agree(cfg):
    """The chunked scan from a carried state equals the one-row
    recurrence applied row by row (the decode program's form)."""
    rng = np.random.default_rng(16)
    b, k, h, p, g, n = 2, 12, 4, 16, 2, 16
    x = jnp.asarray(rng.standard_normal((b, k, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, k, h)), jnp.float32)
    dt = dt.at[1, 9:].set(0.0)                       # dead rows
    A = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, k, g, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, k, g, n)), jnp.float32)
    D = jnp.ones((h,), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((b, h, p, n)), jnp.float32)
    y, s = F.ssd_chunk_scan(x, dt, A, B, C, D, s0)
    st, ys = s0, []
    for t in range(k):
        yt, st = F.ssd_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, st)
        ys.append(yt)
    assert np.abs(np.asarray(y) - np.stack(ys, 1)).max() < 1e-4
    assert np.abs(np.asarray(s) - np.asarray(st)).max() < 1e-4


def test_what_cannot_be_honoured_is_refused_or_switched_off(model, caplog):
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving.engine"):
        eng = engine(model)
    assert eng.stats()["prefix_cache"] is False
    assert eng.stats()["recurrent_state"] is True
    assert eng.pool.cache_enabled is False
    assert sum("prefix cache is off" in r.message
               for r in caplog.records) == 1
    for kw in ({"speculative": 3}, {"host_tier": True}, {"lora": True},
               {"kv_dtype": "int8"}, {"kv_quant": True, "kv_dtype": None},
               {"snapshot_store": object()}):
        with pytest.raises(RecurrentStateError):
            engine(model, **kw)
    for kw in ({"tp": 2}, {"pp": 2}):
        with pytest.raises(TPConfigError, match="NemotronHConfig"):
            engine(model, **kw)
    with pytest.raises(RecurrentStateError):
        eng.add_request(prompt(8), 4, prefill_only=True)
    with pytest.raises(RecurrentStateError):
        eng.save_snapshot("/nonexistent/never-written")
    with pytest.raises(RecurrentStateError):
        eng.restore("/nonexistent/never-read")
    with pytest.raises(RecurrentStateError):
        eng.restore_request(None)
    assert not RecurrentStateError.retryable


def test_a_uniform_decoder_gets_no_state_and_its_old_programs():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    eng = ServingEngine(LlamaForCausalLM(llama_tiny()), num_pages=16,
                        page_size=8, max_slots=2)
    assert eng.pool.state == [] and eng.stats()["recurrent_state"] is False
    assert eng.stats()["prefix_cache"] is True
    assert len(eng._warm_args("decode")) == 11      # no state argument
    assert "state_slots" not in eng.audit_pool()
