"""Weights from the seed, and the plain reference against the program at
a tiny size (the reference makes its own weights: it is handed none)."""

import json
import os

import jax.numpy as jnp
import numpy as np

from benchmarks import weights as W
from benchmarks.families import llama_dense as fam
from benchmarks.reference import llama_dense as ref
from benchmarks.tests.conftest import DATA


def _tiny():
    with open(os.path.join(DATA, "tiny_serve.json")) as f:
        return json.load(f)


def test_leaf_alone_equals_leaf_in_the_one_call():
    shapes = {"a.weight": (64, 128), "b.weight": (128, 32), "n.weight": (64,)}
    big = 2 ** 31 + 12345
    w = W.make_weights(big, shapes, jnp.bfloat16)
    for k, shp in shapes.items():
        alone = W.make_leaf(np.uint32(W.leaf_salt(big, k)), shp, jnp.bfloat16)
        assert np.array_equal(np.asarray(w[k], np.float32),
                              np.asarray(alone, np.float32))
    a = np.asarray(w["a.weight"], np.float32)
    assert abs(a.std() - W.STD) < 2e-3 and abs(a.mean()) < 2e-3
    assert abs(np.asarray(w["n.weight"], np.float32).mean() - 1.0) < 0.02
    other = W.make_weights(big + 1, shapes, jnp.bfloat16)
    assert not np.array_equal(np.asarray(other["a.weight"], np.float32), a)


def test_reference_logits_match_the_program():
    cfg = _tiny()
    seed = 2 ** 31 + 9
    model = fam.build_model(
        cfg, W.make_weights(seed, fam.param_shapes(cfg), jnp.bfloat16))
    model.eval()
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 48))
    got = np.asarray(model(jnp.asarray(ids, jnp.int32)).astype(jnp.float32))[0]
    want = ref.logits_rows(seed, cfg, [ids[0].tolist()], [0])[0]
    assert want.shape == got.shape
    # bf16 program against the float32 reference
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()


def test_int8_control_differs_from_the_reference():
    cfg = _tiny()
    seq = list(range(3, 40))
    full = ref.logits_rows(5, cfg, [seq], [0])[0]
    low = ref.logits_rows(5, cfg, [seq], [0], precision="int8")[0]
    err = np.abs(full - low).max()
    assert 0 < err < 0.2 * np.abs(full).max()
