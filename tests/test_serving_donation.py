"""Both step programs take the K/V pool to write in place (SERVING.md
"Who owns the pool's arrays").

Every compiled body (plain, recurrent state, tp, pp, int8 K/V; decode
and mixed) donates the page pairs, and the state where there is one:
the arrays the engine held before a step are deleted after it, what the
step returned stands in their place, the pool audits clean and the
streams are what the parity tests of each body already pin
(``generate()``; for the recurrent family, which has no ``generate()``,
the same two programs compiled without donation). The compiler's own
counter says the mechanism engaged: ``alias_size_in_bytes`` of each
program covers the pool. On the described chip the same is compiled at
the cell's pool shape in tests/test_tpu_compile.py.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                    # noqa: E402
from benchmarks import weights as W                        # noqa: E402
from benchmarks.families import nemotron_h as fam          # noqa: E402
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny  # noqa: E402
from paddle_tpu.serving import ServingEngine               # noqa: E402

BODIES = ("plain", "state", "tp2", "pp2", "int8")
PROMPTS = {"decode": (11, 5), "mixed": (19, 7)}     # two requests a case
MAX_NEW = 6


def _llama(mp_axis):
    pt.seed(123)
    m = LlamaForCausalLM(llama_tiny(dtype="float32", mp_axis=mp_axis,
                                    fsdp_axis=None))
    m.eval()
    return m


def _nemotron_h():
    with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                           "tiny_nemotron_h_serve_f32.json")) as f:
        cfg = json.load(f)
    m = fam.build_model(cfg, W.make_weights(
        2 ** 31 + 9, fam.param_shapes(cfg), jnp.float32))
    m.eval()
    return m


def _build(body):
    """(model, engine arguments, arguments of the reference ``generate``)"""
    if body == "state":
        return _nemotron_h(), dict(page_size=16, max_pages_per_slot=16,
                                   kv_dtype=jnp.float32), None
    model = _llama("mp" if body in ("tp2", "pp2") else None)
    kw = {"tp2": dict(tp=2), "pp2": dict(pp=2),
          "int8": dict(kv_quant=True)}.get(body, {})
    return model, dict(page_size=8, **kw), (
        dict(kv_dtype="int8") if body == "int8" else {})


def _undonated(eng):
    """The same two programs without donation: what the recurrent body
    is compared with."""
    eng._decode_step = jax.jit(eng._decode_step.__wrapped__)
    eng._mixed_step = jax.jit(eng._mixed_step.__wrapped__)
    return eng


@pytest.fixture(scope="module")
def engines():
    """One engine a body, built on first use and shared by its two
    cases, with the function that gives a prompt's reference stream."""
    built = {}

    def get(body):
        if body not in built:
            model, args, gen = _build(body)

            def mk():
                return ServingEngine(model, num_pages=64, max_slots=4,
                                     prefill_chunk=16, **args)
            if gen is None:
                twin = _undonated(mk())

                def ref(prompt):
                    rid = twin.add_request(prompt, MAX_NEW,
                                           eos_token_id=None)
                    return twin.run_to_completion(max_steps=100)[rid]
            else:
                def ref(prompt):
                    out = model.generate(jnp.asarray([prompt]),
                                         max_new_tokens=MAX_NEW, **gen)
                    return np.asarray(out)[0, len(prompt):].tolist()
            built[body] = (mk(), ref)
        return built[body]
    return get


def _leaves(eng):
    return jax.tree_util.tree_leaves((eng.pool.pools, eng.pool.state))


@pytest.mark.parametrize("program", ["decode", "mixed"])
@pytest.mark.parametrize("body", BODIES)
def test_a_step_consumes_the_pool_it_was_given(engines, monkeypatch, body,
                                               program):
    eng, ref = engines(body)
    name = f"_{program}_step"
    inner = getattr(eng, name)
    calls = []

    def spy(*args):
        # the page pairs, and the state of a recurrent model, as given
        given = jax.tree_util.tree_leaves(
            args[1:3] if eng._recurrent else args[1])
        assert given and not any(a.is_deleted() for a in given)
        out = inner(*args)
        calls.append(all(a.is_deleted() for a in given))
        return out
    spy._cache_size = inner._cache_size
    monkeypatch.setattr(eng, name, spy)

    rng = np.random.default_rng([3, BODIES.index(body), len(program)])
    prompts = [rng.integers(1, 250, n).tolist() for n in PROMPTS[program]]
    before = _leaves(eng)
    rids = [eng.add_request(p, MAX_NEW, eos_token_id=None) for p in prompts]
    out = eng.run_to_completion(max_steps=100)
    monkeypatch.undo()

    assert calls and all(calls)
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in _leaves(eng))
    eng.audit_pool()
    assert [out[r] for r in rids] == [ref(p) for p in prompts]
    assert eng.step_program_counts() == {"decode": 1, "mixed": 1}

    # the compiler's own counter: the program aliases at least its
    # device's share of the pool (and of the state) to its results
    mem = eng.lower_step_programs()[program].compile().memory_analysis()
    shards = eng.pool.tp_degree * eng.pool.pp_degree
    assert mem.alias_size_in_bytes >= sum(
        a.nbytes for a in _leaves(eng)) // shards


@pytest.mark.parametrize("writer", ["rewind", "cow_into"])
def test_an_eager_writer_never_holds_two_pools(monkeypatch, writer):
    """``rewind`` / ``cow_into`` stay eager (each call builds a new
    array from the current one), but replace the pool pair by pair:
    when layer ``i`` is rewritten the layers before it already name
    their new arrays, so no second whole pool stands beside the first
    (on the chip a whole new list held 2.17 GB more: PERF.md, PR 34)."""
    from paddle_tpu.serving import KVCachePool, kv_cache
    pool = KVCachePool(num_layers=3, num_pages=8, page_size=4,
                       num_kv_heads=2, head_dim=16)
    old = [a for pair in pool.pools for a in pair]
    still_old = []

    def spied(real):
        def spy(arr, *args):
            still_old.append(sum(any(a is o for o in old)
                                 for pair in pool.pools for a in pair))
            return real(arr, *args)
        return spy
    monkeypatch.setattr(kv_cache, "_page_copy", spied(kv_cache._page_copy))
    monkeypatch.setattr(KVCachePool, "_pos_zero",
                        staticmethod(spied(KVCachePool._pos_zero)))
    pages = pool.alloc(3)
    {"rewind": lambda: pool.rewind(pages, 5, 9),
     "cow_into": lambda: pool.cow_into(pages[0], pages[1])}[writer]()
    assert still_old == [6, 6, 4, 4, 2, 2]
    assert not any(a is o for o in old
                   for pair in pool.pools for a in pair)
    pool.release(pages)
    pool.audit()


@pytest.mark.parametrize("layout", ["plain", "int8", "stacked"])
def test_scrub_is_one_program_that_writes_the_pool_in_place(layout):
    """``scrub`` (every eviction runs it) is one compiled program of one
    shape with the pool donated: the arrays held before it are deleted,
    a list of one page, of a few and of more than the program's width
    compile nothing after ``warm_scrub()``, the scrubbed pages read
    zero and every other page keeps its content (on the chip the eager
    scrub cost 76 ms an eviction and compiled inside the window:
    PERF.md, PR 34)."""
    from paddle_tpu.serving import KVCachePool, kv_cache
    n = 2 * kv_cache._SCRUB_WIDTH + 8
    pool = KVCachePool(num_layers=2, num_pages=n, page_size=4,
                       num_kv_heads=2, head_dim=16, dtype=jnp.float32,
                       quantized=layout == "int8",
                       pp_degree=2 if layout == "stacked" else 1)
    held = pool.alloc(n - 1)
    pool.pools = jax.tree.map(jnp.ones_like, pool.pools)
    pool.warm_scrub()
    compiled = kv_cache._scrub_in_place._cache_size()
    gone = []
    for pages in ([3], [5, 4, 5, 9], list(range(10, n - 1))):
        before = jax.tree.leaves(pool.pools)
        pool.scrub(pages)
        assert all(a.is_deleted() for a in before)
        gone += pages
    assert kv_cache._scrub_in_place._cache_size() == compiled
    zero = np.isin(np.arange(n), gone + [0])
    for a in jax.tree.leaves(pool.pools):
        a = np.moveaxis(np.asarray(a), 1 if pool.stacked else 0, 0)
        assert not a[zero].any() and (a[~zero] == 1).all()
    pool.free(held)
    pool.audit()
