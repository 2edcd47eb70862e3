"""Traffic kind ``train_steps``: one ``jit.TrainStep`` with its state,
driven from the seed through its first steps (whose readings the
reference follows) and then, the same object, through the window. The
generator, the driver, the end-to-end arithmetic and the cell's ``check``.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np


class TrainSteps:
    """``distinct_batches`` batches of [batch, seq] token ids, fed round
    robin: step k trains on ``batch_of(k)``; labels are the ids."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.batch, self.seq = int(spec["batch"]), int(spec["seq"])
        rng = np.random.default_rng([int(seed), 3])
        self.ids = rng.integers(
            0, vocab, (int(spec["distinct_batches"]), self.batch, self.seq),
            dtype=np.int32)

    def batch_of(self, step: int) -> np.ndarray:
        return self.ids[step % len(self.ids)]


def make_traffic(spec: dict, seed: int, vocab: int) -> TrainSteps:
    return TrainSteps(spec, seed, vocab)


def _leaf_norms(tree: dict) -> dict:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(t):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in t.items()}

    return {k: float(v) for k, v in norms(tree).items()}


def _delta_norms(tree: dict, seed: int, dtype) -> dict:
    """|leaf - its initial value|, the initial value made again from the
    seed (nothing of the start is kept on the device)."""
    import jax
    import jax.numpy as jnp

    from benchmarks import weights as W

    names = sorted(tree)
    salts = np.asarray([W.leaf_salt(seed, k) for k in names], np.uint32)

    @jax.jit
    def deltas(t, salts):
        out = {}
        for i, k in enumerate(names):
            p0 = W.make_leaf(salts[i], t[k].shape, dtype).astype(jnp.float32)
            out[k] = jnp.sqrt(jnp.sum(jnp.square(
                t[k].astype(jnp.float32) - p0)))
        return out

    return {k: float(v) for k, v in deltas(tree, salts).items()}


def build_step(cfg: dict, seed: int, family, spans):
    import jax.numpy as jnp

    import paddle_tpu as pt
    from benchmarks.weights import make_weights

    tr = cfg["train"]
    with spans.span("setup.weights"):
        w = make_weights(seed, family.param_shapes(cfg),
                         jnp.dtype(cfg["torch_dtype"]))
        remat = ({} if tr["remat"] == "none" else
                 {"recompute": True, "recompute_policy": tr["remat"]})
        model = family.build_model(cfg, w, **remat)
        del w
    hp = tr["adamw"]
    opt = pt.optimizer.AdamW(learning_rate=hp["lr"], beta1=hp["beta1"],
                             beta2=hp["beta2"], epsilon=hp["eps"],
                             weight_decay=hp["weight_decay"],
                             parameters=model)
    step = pt.jit.TrainStep(
        model, opt, lambda logits, labels: model.loss(logits, labels))
    return model, step


def run(cfg, spec, seed, seconds, family, tracer, spans):
    import jax
    import jax.numpy as jnp

    gen = make_traffic(spec, seed, cfg["vocab_size"])
    model, step = build_step(cfg, seed, family, spans)
    with spans.span("setup.batches"):
        batches = [jnp.asarray(b) for b in gen.ids]
    n_check = int(spec["check_steps"])
    readings = {"loss": []}
    beta1 = cfg["train"]["adamw"]["beta1"]
    with spans.span("setup.first_steps"):
        for k in range(n_check):
            ids = batches[k % len(batches)]
            readings["loss"].append(float(step(ids, ids)))
            if k == 0:
                # the first gradient as the optimizer got it: after one
                # step from zero moments, moment1 = (1 - beta1) * g
                readings["grad_norm"] = {
                    n: v / (1.0 - beta1) for n, v in
                    _leaf_norms(step.opt_state["moment1"]).items()}
        master = {k: (m if m is not None else model.param_dict()[k])
                  for k, m in step.opt_state["master"].items()}
        readings["delta_norm"] = _delta_norms(
            master, seed, jnp.dtype(cfg["torch_dtype"]))
        del master
    steps = []
    tokens = gen.batch * gen.seq
    k = n_check
    # what set-up and the run before wrote (the compile cache) goes to disk
    # now: its write-back inside the window stalled single steps for
    # seconds in 5 of 11 runs from a fresh checkout (PERF.md, PR 26)
    os.sync()
    tracer.start()
    t_open = time.perf_counter()
    t_close = t_open + seconds
    while time.perf_counter() < t_close:
        ids = batches[k % len(batches)]
        t0 = time.perf_counter()
        with spans.span("bench.train_step"):
            loss = jax.block_until_ready(step(ids, ids))
        steps.append((t0, time.perf_counter()))
        k += 1
    t_last = steps[-1][1]
    tracer.stop()
    last_loss = float(loss)
    stats = jax.devices()[0].memory_stats() or {}
    record = {
        "t_open": t_open, "t_close": t_close, "t_last": t_last,
        "steps": steps, "tokens_per_step": tokens,
        "batches": [np.asarray(b) for b in gen.ids], "readings": readings,
        "last_loss": last_loss, "failed": int(not np.isfinite(last_loss)),
        "attempted": len(steps),
        "compiled_in_window": int(step._compiled._cache_size()) != 1,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    del step, model, batches, loss
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return record


def end_to_end(rec):
    """The window runs from its opening to the end of the step in flight
    when its seconds were up (``t_last``), as the serving window does:
    every step begun inside it counts whole, its tokens and its time, so
    that the rate does not move in quanta of one step."""
    seconds = rec["t_last"] - rec["t_open"]
    n = len(rec["steps"])
    half = rec["t_open"] + seconds / 2
    return ({"train_tokens_per_s": (n * rec["tokens_per_step"] / seconds,
                                    "tokens/s")},
            {"steps_in_window": n, "window_s": seconds,
             "steps_in_first_half": sum(1 for _, e in rec["steps"]
                                        if e <= half),
             "longest_step_s": max(e - b for b, e in rec["steps"])})


def attempted(rec) -> int:
    return rec["attempted"]


def check(rec, cfg, spec, seed, control=False):
    from benchmarks import check as compare

    return compare.train(cfg, spec, seed, rec, control)
