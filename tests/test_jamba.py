"""Jamba (Mamba-1 mixers, an attention mixer a period, a tied head)
through ``ServingEngine``: the program against the plain reference
(``benchmarks/reference/jamba.py``) at a tiny size on the CPU, on seeded
weights, on logits and states rather than tokens; the selective scan's
three forms against each other; the state semantics a recurrent model
needs from the engine.

Tolerances: the program and the reference are both float32 here (matmul
precision "highest" in the reference, the CPU's float32 in the program)
and differ in the ORDER of sums: paged attention against full attention,
a window carried over chunks against one convolution, fused
multiply-adds. 1e-4 absolute on
logits of magnitude 2 holds that with room (readings are some 3e-6);
where two runs of the SAME program are compared the test asks for equal
bits.
"""

import json
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
from benchmarks.families import jamba as fam              # noqa: E402
from benchmarks.reference import jamba as ref             # noqa: E402
from paddle_tpu.models.jamba import (JambaConfig,          # noqa: E402
                                     JambaExpertsError, jamba_tiny)
from paddle_tpu.nn import functional as F                 # noqa: E402
from paddle_tpu.nn.functional import ssm                  # noqa: E402
from paddle_tpu.ops.pallas import selective_scan as kernel  # noqa: E402
from paddle_tpu.serving import ServingEngine              # noqa: E402
from paddle_tpu.serving.errors import (RecurrentStateError,  # noqa: E402
                                       TPConfigError)
from paddle_tpu.serving.kv_cache import HybridCache        # noqa: E402

SEED = 2 ** 31 + 9
TOL = 1e-4
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
                           "tiny_jamba_serve_f32.json")) as f:
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
    return np.random.default_rng([11, salt]).integers(0, vocab, n).tolist()


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
        assert not np.asarray(cache.counts).any()    # no expert layer
        out = {s: np.asarray(logits[s, :len(t)]) for s, t in rows.items()}
        for s, t in rows.items():
            self.lens[s] += len(t)
        return out

    def restart(self, slot):
        self.lens[slot] = 0


# -- the model against the reference -----------------------------------------

def test_cache_free_forward_matches_the_reference(cfg, model):
    ids = [prompt(48, 1), prompt(48, 2)]
    got = np.asarray(model(jnp.asarray(ids, jnp.int32)))
    want = ref.logits_rows(SEED, cfg, ids, [0, 0])
    for g, w in zip(got, want):
        assert np.abs(g - w).max() < TOL
    assert np.abs(want[0]).max() > 0.1          # not a comparison of noughts


def test_the_three_norms_and_the_conv_are_heard(cfg):
    """What the planted fault leaves out moves the reference's own
    output: a comparison with it can see the norms."""
    lw = ref.layer_weights(SEED, cfg, 0)
    u = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 24, cfg["hidden_size"])), jnp.float32)
    with_norms = np.asarray(ref.mamba(u, lw, cfg))
    without = np.asarray(ref.mamba(u, lw, cfg, norms=False))
    assert np.abs(with_norms - without).max() > 0.05 * np.abs(with_norms).max()


def test_bf16_weights_and_reference_agree_leaf_for_leaf():
    cfg = dict(tiny_cfg(), torch_dtype="bfloat16")
    m = fam.build_model(cfg, W.make_weights(
        SEED, fam.param_shapes(cfg), jnp.bfloat16))
    sd = m.state_dict()
    assert "lm_head.weight" not in sd            # the head is the embedding
    for k, shp in fam.param_shapes(cfg).items():
        assert np.array_equal(np.asarray(sd[k], np.float32),
                              np.asarray(ref._leaf(SEED, k, shp, cfg))), k
    a = -np.exp(np.asarray(sd["model.layers.0.mamba.A_log"], np.float32))
    assert np.allclose(a[7], -np.arange(1, 17), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(
        sd["model.layers.0.mamba.dt_proj.bias"], np.float32)))
    assert ((dt >= 1e-3 * 0.99) & (dt <= 0.1 * 1.01)).all()
    assert np.asarray(sd["model.layers.0.mamba.conv1d_weight"],
                      np.float32).std() > 0.25


def test_prefill_then_decode_through_the_pools_matches_the_reference(
        cfg, model):
    """Uneven chunks of 16, 11 and 5 prompt rows beside another slot's,
    then 6 one-row decode steps (the recurrence's other form), against
    the reference's one pass."""
    slots = Slots(model, engine(model))
    seq, other = prompt(38, 3), prompt(31, 4)
    got = [slots.run({1: seq[:16], 3: other[:9]})[1],
           slots.run({1: seq[16:27], 3: other[9:25]})[1],
           slots.run({1: seq[27:32]})[1]]
    got += [slots.run({1: [t], 3: [o]})[1]
            for t, o in zip(seq[32:], other[25:])]
    got = np.concatenate(got)
    want = ref.logits_rows(SEED, cfg, [seq], [0])[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_engine_chunks_and_decodes_to_the_reference_s_best_tokens(cfg, model):
    """Through ``add_request`` / ``step``: prompts of 37 and 21 tokens in
    chunks of 16, then decoding. The engine hands out tokens, so the
    reference is asked what the comparison of a run asks: by how much
    each served token's logit lies under the reference's best."""
    eng = engine(model)
    served = [(prompt(37, 5), 12), (prompt(21, 6), 9)]
    rids = [eng.add_request(p, n) for p, n in served]
    out = eng.run_to_completion()
    gaps, _ = ref.serve_gaps(SEED, cfg, [(p, out[r]) for (p, _), r
                                         in zip(served, rids)])
    assert [len(g) for g in gaps] == [12, 9]
    assert max(float(g.max()) for g in gaps) < TOL
    assert eng.step_program_counts() == {"decode": 1, "mixed": 1}


def test_chunking_or_one_pass_gives_the_same_logits_and_state(model):
    seq = prompt(135, 7)
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
    state is what 5 rows alone leave; slot 1, inactive, keeps what it
    had, to the bit, through a mixed pass and through a decode pass."""
    a, b = Slots(model, engine(model)), Slots(model, engine(model))
    first, more, other = prompt(9, 8), prompt(5, 9), prompt(16, 10)
    for s in (a, b):
        s.run({0: first, 1: first})
    kept = [[np.asarray(x[1]) for x in layer] for layer in a.pool.state]
    assert np.abs(kept[0][1]).max() > 0
    a.run({0: more, 2: other})          # 16 wide, 5 live in slot 0
    b.run({0: more})                    # 5 wide
    for la, lb, lk in zip(a.pool.state, b.pool.state, kept):
        for x, y, k in zip(la, lb, lk):
            # another width of the pass, another order of the sums
            assert np.abs(np.asarray(x[0]) - np.asarray(y[0])).max() < 1e-5
            assert np.array_equal(np.asarray(x[1]), k)
    a.run({0: [3]})
    for la, lk in zip(a.pool.state, kept):
        for x, k in zip(la, lk):
            assert np.array_equal(np.asarray(x[1]), k)


def test_a_reused_slot_reads_nothing_of_its_last_tenant(model):
    used, fresh = Slots(model, engine(model)), Slots(model, engine(model))
    used.run({2: prompt(40, 11)})
    assert float(jnp.abs(used.pool.state[0][1][2]).max()) > 0
    used.restart(2)                     # the next tenant: position 0
    seq = prompt(21, 12)
    a = np.concatenate([used.run({2: seq[:16]})[2], used.run({2: seq[16:]})[2]])
    b = np.concatenate([fresh.run({2: seq[:16]})[2],
                        fresh.run({2: seq[16:]})[2]])
    assert np.array_equal(a, b)


def test_engine_reuses_a_slot_and_recomputes_after_preemption(model):
    reqs = [(prompt(37, 13), 9), (prompt(20, 14), 30), (prompt(28, 15), 12)]
    alone = []
    for p, n in reqs:
        eng = engine(model)
        rid = eng.add_request(p, n)
        alone.append(eng.run_to_completion()[rid])
    eng = engine(model, max_slots=1)
    rids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run_to_completion()
    assert [out[r] for r in rids] == alone
    assert eng.audit_pool()["state_slots"] == 0
    eng = engine(model, num_pages=8, max_slots=3)
    rids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run_to_completion()
    assert eng.stats()["preemptions"] > 0
    assert [out[r] for r in rids] == alone


def test_pool_holds_what_the_config_declares(cfg, model):
    eng = engine(model)
    d, n = 2 * cfg["hidden_size"], cfg["mamba_d_state"]
    layers = model.config.cache_layers()
    assert [kind for kind, *_ in layers] == (
        ["state", "state", "pages", "state"] * 2)
    assert layers[2] == ("pages", 1, 128)
    assert layers[0] == ("state", (((3, d), "float32"), ((n, d), "float32")))
    st = eng.stats()["pool"]
    assert st["state_layers"] == 6
    assert st["state_bytes_per_slot"] == 6 * (3 * d + n * d) * 4
    assert len(eng.pool.pools) == 2 and eng.pool.pools[0][0].shape[2:] == (1, 128)
    # the channels fill the lanes: [slots, d_state, d_inner]
    assert eng.pool.state[0][1].shape == (4, n, d)
    jax.block_until_ready(eng.pool.state)


def test_what_cannot_be_honoured_is_refused_or_switched_off(model):
    eng = engine(model)
    assert eng.stats()["prefix_cache"] is False
    assert eng.stats()["recurrent_state"] is True
    for kw in ({"speculative": 3}, {"host_tier": True}, {"lora": True},
               {"kv_dtype": "int8"}, {"snapshot_store": object()}):
        with pytest.raises(RecurrentStateError):
            engine(model, **kw)
    for kw in ({"tp": 2}, {"pp": 2}):
        with pytest.raises(TPConfigError, match="JambaConfig"):
            engine(model, **kw)


def test_a_traced_engine_counts_the_scan_s_live_rows(model):
    """Every traced mixed dispatch bumps ``scan_rows_dispatched`` by the
    rows of its rectangle and ``scan_rows_live`` by the rows that carry
    a token, both times the layers that keep a state: 37 + 5 prompt rows
    in chunks, and a decode lane's one row where it shares a step."""
    from paddle_tpu.observability.trace import Tracer
    tr = Tracer()
    eng = engine(model, tracer=tr)
    eng.add_request(prompt(37, 16), 4)
    eng.add_request(prompt(5, 17), 4)
    while eng.scheduler.running or eng.scheduler.queue_depth:
        eng.step()
    c = tr.counters
    layers = len(eng.pool.state)
    assert layers == 6
    assert c["scan_rows_dispatched"] == (
        c["mixed_steps"] * eng.max_slots * 16 * layers)
    assert (42 * layers <= c["scan_rows_live"]
            <= (42 + c["mixed_steps"]) * layers)
    assert c["state_slots_live"] > 0 and not c.get("expert_rows_routed")


def test_routed_experts_are_refused_by_name():
    with pytest.raises(JambaExpertsError, match="num_experts=16"):
        jamba_tiny(num_experts=16, num_experts_per_tok=2)
    assert issubclass(JambaExpertsError, NotImplementedError)
    published = JambaConfig()
    assert published.cache_layers().count(("pages", 1, 128)) == 2
    assert [i for i in range(28) if published.is_attention(i)] == [7, 21]


# -- the selective scan's forms ------------------------------------------------

def scan_inputs(b=3, k=16, d=256, n=16, salt=0):
    rng = np.random.default_rng([16, salt])
    f32 = jnp.float32
    return dict(
        x=jnp.asarray(rng.standard_normal((b, k, d)), f32),
        dt=jnp.asarray(rng.uniform(0.001, 0.1, (b, k, d)), f32),
        A=-jnp.asarray(rng.uniform(1, 16, (d, n)), f32),
        B=jnp.asarray(rng.standard_normal((b, k, n)), f32),
        C=jnp.asarray(rng.standard_normal((b, k, n)), f32),
        D=jnp.asarray(rng.standard_normal((d,)), f32),
        state=jnp.asarray(rng.standard_normal((b, n, d)), f32))


def test_two_chunks_equal_one_pass_and_the_step_row_by_row():
    a = scan_inputs()
    full = jnp.full((3,), 16, jnp.int32)
    y, h = F.selective_scan_rows(**a, n_live=full)
    half = jnp.full((3,), 8, jnp.int32)
    part = {k: v[:, :8] if k in ("x", "dt", "B", "C") else v
            for k, v in a.items()}
    rest = {k: v[:, 8:] if k in ("x", "dt", "B", "C") else v
            for k, v in a.items()}
    y1, h1 = F.selective_scan_rows(**part, n_live=half)
    y2, h2 = F.selective_scan_rows(**dict(rest, state=h1), n_live=half)
    assert np.array_equal(np.asarray(y), np.concatenate([y1, y2], axis=1))
    assert np.array_equal(np.asarray(h), np.asarray(h2))
    st, ys = a["state"], []
    for t in range(16):
        yt, st = F.selective_scan_step(a["x"][:, t], a["dt"][:, t], a["A"],
                                       a["B"][:, t], a["C"][:, t], a["D"], st)
        ys.append(yt)
    assert np.abs(np.asarray(y) - np.stack(ys, 1)).max() < 1e-5
    assert np.abs(np.asarray(h) - np.asarray(st)).max() < 1e-5


@pytest.mark.parametrize("n_live", [[16, 5, 0], [0, 0, 0], [0, 16, 1],
                                    [1, 0, 9]],
                         ids=["first_live", "none_live", "first_dead",
                              "middle_dead"])
def test_the_kernel_in_interpret_mode_equals_the_scan(n_live):
    """Dead rows give exact zeros, a slot with no live row keeps its
    state to the bit (whatever block its grid step names), and the live
    part agrees with the ``lax.scan`` form."""
    a = scan_inputs(salt=1)
    n_live = jnp.asarray(n_live, jnp.int32)
    assert kernel.kernel_applicable(a["x"].shape, a["state"].shape)
    want_y, want_h = ssm._selective_scan_rows_xla(**a, n_live=n_live)
    y, h = kernel.selective_scan_tpu(**a, n_live=n_live)
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-5
    assert np.abs(np.asarray(h) - np.asarray(want_h)).max() < 1e-5
    for s, n in enumerate(np.asarray(n_live)):
        assert not np.asarray(y[s, n:]).any()
        assert np.asarray(want_y[s, :n]).any() == bool(n)
        if n == 0:
            assert np.array_equal(np.asarray(h[s]), np.asarray(a["state"][s]))
            assert np.array_equal(np.asarray(want_h[s]),
                                  np.asarray(a["state"][s]))


def test_a_dead_slot_names_a_live_slot_s_blocks():
    f = lambda v: kernel.dead_slot_blocks(jnp.asarray(v, jnp.int32)).tolist()
    assert f([0, 3, 0, 0, 1, 0]) == [1, 1, 1, 1, 4, 4]
    assert f([2, 2, 2]) == [0, 1, 2]
    assert f([0, 0, 0]) == [0, 0, 0]
    assert not kernel.kernel_applicable((4, 1, 256), (4, 16, 256))
    assert not kernel.kernel_applicable((4, 16, 200), (4, 16, 200))
