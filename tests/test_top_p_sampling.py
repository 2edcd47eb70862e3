"""``top_p_sampling`` takes its sorted probabilities from the sort itself.

Three things are held here (PERF.md section 6, PR 31):

1. THE SAME BITS — the shipped function returns the ``(values, indices)``
   of the formula it replaced (``argsort`` and a ``take_along_axis`` over
   the whole vocabulary, kept below as ``reference_top_p``), under ``jit``
   and under ``vmap`` over rows as ``engine._sample_rows`` calls it.
2. THE SAME STREAMS — a seeded ``top_p < 1`` request and a greedy request
   in one batch emit, through the mixed program's final prompt chunk and
   through the decode program, with and without a preempt-and-recompute,
   the tokens an engine whose ``_sample_rows`` traces the reference emits.
3. THE MECHANISM STAYS — under the ``sampler`` scope the lowered decode
   and mixed programs hold one ``sort`` for their one ``_sample_rows`` call
   and no gather that reads the vocabulary axis through a vocabulary of
   indices. Counts from the lowered programs, on the CPU: no rates.
"""

import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jaxlib.mlir import ir

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                             # noqa: E402
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny    # noqa: E402
from paddle_tpu.ops import random as ops_random                     # noqa: E402
from paddle_tpu.serving import SamplingParams, ServingEngine        # noqa: E402
from paddle_tpu.serving.engine import _sample_rows                  # noqa: E402

# vocabulary sizes of the tiny engines: no other dimension of their step
# programs has these sizes, so "an axis of V" names the vocabulary
LLAMA_V = 520
NEMOTRON_V = 250


def reference_top_p(x, ps, threshold=None, seed=None, key=None, name=None):
    """The formula ``top_p_sampling`` had before PR 31, word for word: the
    sort's keys thrown away and fetched again by a gather over V."""
    x = jnp.asarray(x)
    ps = jnp.asarray(ps).reshape(-1, 1)
    order = jnp.argsort(-x, axis=-1)
    sorted_p = jnp.take_along_axis(x, order, axis=-1)
    prefix = jnp.cumsum(sorted_p, axis=-1) - sorted_p
    keep = prefix < ps
    keep = keep.at[:, 0].set(True)
    if threshold is not None:
        thr = jnp.asarray(threshold).reshape(-1, 1)
        keep = keep & (sorted_p >= thr)
        keep = keep.at[:, 0].set(True)
    probs = jnp.where(keep, sorted_p, 0.0)
    probs = probs / jnp.maximum(jnp.sum(probs, -1, keepdims=True), 1e-9)
    pick = jax.random.categorical(key, jnp.log(jnp.maximum(probs, 1e-38)), -1)
    idx = jnp.take_along_axis(order, pick[:, None], axis=-1)
    val = jnp.take_along_axis(x, idx, axis=-1)
    return val, idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# 1. the same bits
# ---------------------------------------------------------------------------

ROWS = 4


def _probabilities(source, V):
    """[ROWS, V] float32 probabilities."""
    k = jax.random.key(V + len(source))
    if source == "float32":
        lg = jax.random.normal(k, (ROWS, V), jnp.float32) * 3.0
        return jax.nn.softmax(lg, axis=-1)
    if source == "bfloat16":
        # as engine._sample_rows makes them: logits in the model's dtype,
        # widened, over a temperature. Among 32768 bfloat16 logits many
        # are equal, so these rows hold ties of their own
        lg = (jax.random.normal(k, (ROWS, V), jnp.float32) * 3.0
              ).astype(jnp.bfloat16)
        return jax.nn.softmax(lg.astype(jnp.float32) / 0.8, axis=-1)
    if source == "ties":
        # every value several times over, exact zeros among them: the
        # stable sort's order among equals is the index order
        w = jax.random.randint(k, (ROWS, V), 0, 4).astype(jnp.float32)
        w = w.at[:, V // 2].set(5.0)              # no row is all zeros
        w = w.at[0].set(1.0)                      # one row is all ties
        return w / w.sum(-1, keepdims=True)
    raise KeyError(source)


def _thresholds(V):
    # none kept but the top-1 (1.0), a floor that cuts (2 / V), none cut
    return jnp.asarray([0.0, 2.0 / V, 0.01, 1.0], jnp.float32)


def _under_jit(fn):
    return jax.jit(lambda x, ps, thr, key: fn(x, ps, threshold=thr, key=key))


def _under_vmap(fn):
    # as engine._sample_rows calls it: one row at a time, each with the
    # key of its own stream
    def run(x, ps, thr, key):
        def row(xr, p, t, cnt):
            k = jax.random.fold_in(key, cnt)
            v, i = fn(xr[None], p[None],
                      threshold=None if t is None else t[None], key=k)
            return v[0, 0], i[0, 0]
        return jax.vmap(row, in_axes=(0, 0, None if thr is None else 0, 0))(
            x, ps, thr, jnp.arange(x.shape[0]))
    return jax.jit(run)


@functools.cache
def _compiled(mode, which):
    fn = ops_random.top_p_sampling if which == "shipped" else reference_top_p
    return {"jit": _under_jit, "vmap": _under_vmap}[mode](fn)


@pytest.mark.parametrize("mode", ["jit", "vmap"])
@pytest.mark.parametrize("with_threshold", [False, True],
                         ids=["no_threshold", "threshold"])
@pytest.mark.parametrize("ps", [0.0, 0.3, 0.9, 1.0])
@pytest.mark.parametrize("V", [7, 32768])
@pytest.mark.parametrize("source", ["float32", "bfloat16", "ties"])
def test_same_bits_as_argsort_and_gather(source, V, ps, with_threshold, mode):
    x = _probabilities(source, V)
    thr = _thresholds(V) if with_threshold else None
    p = jnp.full((ROWS,), ps, jnp.float32)
    for key_seed in (0, 2 ** 31 + 5):
        key = jax.random.key(key_seed)
        v, i = _compiled(mode, "shipped")(x, p, thr, key)
        rv, ri = _compiled(mode, "reference")(x, p, thr, key)
        assert i.dtype == ri.dtype == jnp.int32 and v.dtype == rv.dtype
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(v).view(np.uint32),
                                      np.asarray(rv).view(np.uint32))
        if ps == 0.0:            # the top-1 token alone: greedy
            np.testing.assert_array_equal(np.asarray(i).reshape(-1),
                                          np.asarray(jnp.argmax(x, -1)))


# ---------------------------------------------------------------------------
# 2. the same streams through an engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama_model():
    pt.seed(123)
    model = LlamaForCausalLM(dataclasses.replace(
        llama_tiny(dtype="float32", mp_axis=None, fsdp_axis=None),
        vocab_size=LLAMA_V))
    model.eval()
    return model


def _streams(model, seed, preempt):
    """Token streams of (a greedy request, a seeded top_p < 1 request on
    another prompt and, where the pool has room, a greedy request on the
    sampled one's prompt), all in one batch. With ``preempt`` the pool
    holds 6 pages where the two requests need 4 + 5 at full length: decode
    growth preempts the younger, the sampled one, which is recomputed."""
    rng = np.random.default_rng([31, seed % 1000])
    sampled_prompt = rng.integers(0, LLAMA_V, 7).tolist()
    other_prompt = rng.integers(0, LLAMA_V, 6).tolist()
    sp = SamplingParams(do_sample=True, top_p=0.9, temperature=0.8, seed=seed)
    if preempt:
        eng = ServingEngine(model, num_pages=7, page_size=4, max_slots=2,
                            max_pages_per_slot=6, prefill_chunk=4)
    else:
        eng = ServingEngine(model, num_pages=32, page_size=4, max_slots=3,
                            max_pages_per_slot=6, prefill_chunk=4)
    rids = [eng.add_request(other_prompt, 10),
            eng.add_request(sampled_prompt, 10, sampling=sp)]
    if not preempt:
        rids.append(eng.add_request(sampled_prompt, 10))
    res = eng.run_to_completion(max_steps=500)
    if preempt:
        assert eng.scheduler.num_preemptions > 0
        assert eng.request(rids[1]).preemptions > 0
    else:
        assert eng.scheduler.num_preemptions == 0
    assert eng.step_program_counts() == {"decode": 1, "mixed": 1}
    return [res[r] for r in rids]


@pytest.mark.parametrize("preempt", [False, True],
                         ids=["roomy_pool", "preempt_and_recompute"])
@pytest.mark.parametrize("seed", [3, 42, 2 ** 31 - 7])
def test_engine_streams_are_the_reference_formulas(llama_model, seed, preempt,
                                                   monkeypatch):
    shipped = _streams(llama_model, seed, preempt)
    # each engine traces its own step programs, and _sample_rows looks the
    # function up as it is traced
    traced = []

    def old_formula(*args, **kw):
        traced.append(1)
        return reference_top_p(*args, **kw)

    monkeypatch.setattr(ops_random, "top_p_sampling", old_formula)
    reference = _streams(llama_model, seed, preempt)
    assert len(traced) == 2              # the decode and the mixed program
    greedy, sampled = shipped[0], shipped[1]
    assert len(greedy) == len(sampled) == 10
    # token 0 is sampled by the mixed program on the prompt's final chunk
    # (2 chunks of 4), the others by the decode program or, once
    # preempted, by the recompute's final chunk
    assert shipped == reference
    if not preempt:
        # the sampled row was drawn, not argmax'd: the greedy request on
        # the same prompt reads otherwise
        assert sampled != shipped[2]


# ---------------------------------------------------------------------------
# 3. the mechanism stays: counts from the lowered programs
# ---------------------------------------------------------------------------

def _scope_of(op):
    m = re.match(r'loc\("([^"]*)"', str(op.location))
    return m.group(1) if m else ""


def _shape(value):
    try:
        return list(ir.ShapedType(value.type).shape)
    except ValueError:                   # a token: no shape
        return []


def ops_under(lowered, scope=None):
    """``(name, operand shapes, result shapes, slice sizes of a gather)`` of
    the operations of a lowered program under ``jax.named_scope(scope)``
    (of all of them with ``scope`` None), the bodies of the private
    functions called from there included: ``take_along_axis`` lowers to a
    call, and what it calls names only its own part of the path."""
    module = lowered.compiler_ir()       # alive while its operations are read
    funcs = {ir.StringAttr(op.attributes["sym_name"]).value: op.operation
             for op in module.body.operations
             if op.operation.name == "func.func"}
    found = []

    def visit(op, inside):
        for region in op.regions:
            for block in region:
                for inner in block:
                    o = inner.operation
                    here = inside or f"/{scope}/" in f"/{_scope_of(o)}/"
                    if here:
                        sizes = (list(ir.DenseI64ArrayAttr(
                            o.attributes["slice_sizes"]))
                            if o.name == "stablehlo.gather" else None)
                        found.append((o.name,
                                      [_shape(v) for v in o.operands],
                                      [_shape(v) for v in o.results], sizes))
                    if o.name == "func.call":
                        callee = ir.FlatSymbolRefAttr(
                            o.attributes["callee"]).value
                        visit(funcs[callee], here)
                    else:
                        visit(o, here)

    visit(funcs["main"], scope is None)
    return found


def gathers(ops):
    """(operand shape, result shape, slice sizes) of every gather."""
    return [(operands[0], results[0], sizes)
            for name, operands, results, sizes in ops
            if name == "stablehlo.gather"]


def vocabulary_wide(gathered, V):
    """Of ``gathers()``' rows, those that read the vocabulary axis one
    element an index for a vocabulary of indices: what
    ``take_along_axis(x, order)`` was. A pick of one element a row
    (``order[pick]``: no V in the result) and a copy of whole logit rows
    (``_mixed_tail``'s: a slice of V) are not."""
    return [g for g in gathered
            if V in g[1] and any(n == V and g[2][d] == 1
                                 for d, n in enumerate(g[0]))]


def count(ops, name):
    return sum(op[0] == name for op in ops)


def _lower_alone(fn, B=16, V=32768):
    sd = jax.ShapeDtypeStruct
    return jax.jit(lambda x, ps, key: fn(x, ps, key=key)).lower(
        sd((B, V), jnp.float32), sd((B,), jnp.float32), jax.random.key(0))


def test_top_p_sampling_alone_has_one_sort_and_two_picks():
    ops = ops_under(_lower_alone(ops_random.top_p_sampling))
    assert count(ops, "stablehlo.sort") == 1
    # order[pick], then x[idx] for the returned value: one element a row
    assert [g[1] for g in gathers(ops)] == [[16, 1], [16, 1]]
    assert vocabulary_wide(gathers(ops), 32768) == []


def test_the_count_sees_the_reference_formula():
    # the control of the tests around it: the same count on the formula
    # of before PR 31 finds its gather over the vocabulary
    ops = ops_under(_lower_alone(reference_top_p))
    assert count(ops, "stablehlo.sort") == 1
    assert vocabulary_wide(gathers(ops), 32768) == [
        ([16, 32768], [16, 32768], [1, 1])]


def _lower_sample_rows(S, V=32768):
    sd = jax.ShapeDtypeStruct
    # a function of its own each time: jit's trace cache is keyed on the
    # function, and _sample_rows looks top_p_sampling up as it is traced
    return jax.jit(lambda *a: _sample_rows(*a)).lower(
        sd((S, V), jnp.bfloat16), sd((S,), jnp.float32),
        sd((S,), jnp.float32), sd((S,), jnp.bool_), sd((S,), jnp.int32),
        sd((S,), jnp.int32))


@pytest.mark.parametrize("S", [16, 64])
def test_sample_rows_at_the_cells_sizes(S):
    ops = ops_under(_lower_sample_rows(S))
    assert count(ops, "stablehlo.sort") == 1
    # the value top_p_sampling returns is not used: one pick is left
    assert gathers(ops) == [([S, 1, 32768], [S, 1, 1], [1, 1, 1])]


def _llama_engine(model):
    return ServingEngine(model, num_pages=32, page_size=4, max_slots=3,
                         prefill_chunk=8)


def _nemotron_engine():
    from benchmarks import weights as W
    from benchmarks.families import nemotron_h as fam
    with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                           "tiny_nemotron_h_serve_f32.json")) as f:
        cfg = json.load(f)
    cfg["vocab_size"] = NEMOTRON_V
    model = fam.build_model(cfg, W.make_weights(
        2 ** 31 + 9, fam.param_shapes(cfg), jnp.float32))
    model.eval()
    return ServingEngine(model, num_pages=64, page_size=16, max_slots=4,
                         max_pages_per_slot=16, prefill_chunk=16,
                         kv_dtype=jnp.float32)


@pytest.fixture(scope="module")
def lowered_programs(llama_model):
    return {"llama": (_llama_engine(llama_model).lower_step_programs(),
                      LLAMA_V),
            "nemotron_h": (_nemotron_engine().lower_step_programs(),
                           NEMOTRON_V)}


@pytest.mark.parametrize("program", ["decode", "mixed"])
@pytest.mark.parametrize("family", ["llama", "nemotron_h"])
def test_step_program_sampler_has_one_sort_and_no_vocabulary_wide_gather(
        lowered_programs, family, program):
    programs, V = lowered_programs[family]
    ops = ops_under(programs[program], "sampler")
    # the scope is there and holds the logits
    assert any(V in shape for op in ops for shape in op[2])
    assert count(ops, "stablehlo.sort") == 1     # one _sample_rows call
    found = gathers(ops)
    assert vocabulary_wide(found, V) == []
    picks = [g for g in found if V not in g[1]]
    whole_rows = [g for g in found if V in g[1]]
    assert len(picks) == 1 and picks[0][0][-1] == V      # order[pick]
    # _mixed_tail's copy of the rows whose sample can be emitted
    assert len(whole_rows) == (1 if program == "mixed" else 0)
    assert all(g[2][-1] == V for g in whole_rows)


def test_the_scope_sees_the_reference_formula(llama_model, monkeypatch):
    monkeypatch.setattr(ops_random, "top_p_sampling", reference_top_p)
    for program in _llama_engine(llama_model).lower_step_programs().values():
        found = vocabulary_wide(gathers(ops_under(program, "sampler")),
                                LLAMA_V)
        assert len(found) == 1 and found[0][0][-1] == LLAMA_V
