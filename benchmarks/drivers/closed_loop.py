"""Traffic kind ``closed_loop``: one ``ServingEngine`` under a closed
loop of clients, timed by the harness's own clock after each ``step()``
returns. The generator, the driver, the end-to-end arithmetic and the
cell's ``check``.

From the program it takes the engine's public calls (``add_request``,
``step``, ``warm_programs``) and what a step returns (token events) plus
each request's ``context_len``; TTFT, gaps, rates and step kinds are the
harness's own arithmetic over its own record.
"""

from __future__ import annotations

import gc
import os
import time
from dataclasses import dataclass, field

import numpy as np

from benchmarks import traffic


class ClosedLoop:
    """``clients`` callers, each sending its next request when its last
    one finished. ``request(c, k)`` is client c's k-th request: (prompt
    ids, output tokens). Lengths: ``traffic.quantile_grid`` of the mix's
    two distributions, ``pool`` points each, an output cut so that prompt
    + output stays within ``max_total_tokens``.

    The window opens on a loop that has been running: each stream's FIRST
    request is the rest of a request in flight, the share still to come on
    the fixed grid (c + 0.5) / clients. That is how an equilibrium renewal
    process starts (Cox, Renewal Theory, 1962: seen at a random time, the
    elapsed share of the interval in progress is uniform); without it all
    clients prefill in set-up and none in the first half of the window."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        if spec.get("shared_prefix_tokens", 0):
            raise NotImplementedError("shared prefixes: a later PR")
        if spec.get("sampling", "greedy") != "greedy":
            raise NotImplementedError("sampled traffic: a later PR")
        self.clients = int(spec["clients"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        n = int(spec["pool"])
        self._turn = self.seed % self.clients
        rng = np.random.default_rng([traffic.LAYOUT_SEED, 1])
        self._prompts = rng.permutation(
            traffic.quantile_grid(spec["prompt_tokens"], n))
        self._outputs = rng.permutation(
            traffic.quantile_grid(spec["output_tokens"], n))
        cap = spec.get("max_total_tokens")
        if cap is not None:
            self._outputs = np.minimum(self._outputs,
                                       int(cap) - self._prompts)
            if self._outputs.min() < 2:
                raise ValueError("max_total_tokens leaves a request under "
                                 "2 output tokens")
        self._phase = rng.permutation(self.clients)
        self.max_tokens = int((self._prompts + self._outputs).max())

    def request(self, client: int, k: int) -> tuple[list[int], int]:
        stream = (client + self._turn) % self.clients
        j = (stream + k * self.clients) % len(self._prompts)
        n_out = int(self._outputs[j])
        if k == 0:
            frac = (int(self._phase[stream]) + 0.5) / self.clients
            n_out = max(2, int(round(n_out * frac)))
        rng = np.random.default_rng([self.seed, 2, client, k])
        prompt = rng.integers(0, self.vocab, int(self._prompts[j]))
        return prompt.tolist(), n_out


def make_traffic(spec: dict, seed: int, vocab: int) -> ClosedLoop:
    return ClosedLoop(spec, seed, vocab)


@dataclass
class Req:
    client: int
    k: int
    prompt: list
    n_out: int
    t_submit: float
    rid: str = ""
    tokens: list = field(default_factory=list)
    t_tokens: list = field(default_factory=list)
    ctx: int = 0                     # tokens in the cache, as last read
    reason: str | None = None


@dataclass
class Step:
    t0: float
    t1: float
    mixed: bool
    rows: int                        # token rows the step processed
    attn_keys: int                   # sum over rows of the keys each saw
    decode_contexts: tuple           # keys seen by each decode row
    live_contexts: tuple             # tokens cached by each request still
                                     # live after the step


def build_engine(cfg: dict, seed: int, family, max_tokens: int, spans):
    """Weights on the device from the seed (one jitted call), the
    program's model holding them, the engine, both programs warm."""
    import jax
    import jax.numpy as jnp

    from benchmarks.weights import make_weights
    from paddle_tpu.serving import ServingEngine

    e = cfg["engine"]
    need = -(-max_tokens // e["page_size"]) + 1
    if need > e["max_pages_per_slot"]:
        raise ValueError(f"the traffic's longest request needs {need} pages "
                         f"a slot, the configuration allows "
                         f"{e['max_pages_per_slot']}")
    with spans.span("setup.weights"):
        w = make_weights(seed, family.param_shapes(cfg),
                         jnp.dtype(cfg["torch_dtype"]))
        model = family.build_model(cfg, w)
        model.eval()
        del w
    with spans.span("setup.engine"):
        eng = ServingEngine(model, num_pages=e["num_pages"],
                            page_size=e["page_size"],
                            max_slots=e["max_slots"],
                            max_pages_per_slot=e["max_pages_per_slot"],
                            prefill_chunk=e["prefill_chunk"], tp=e["tp"],
                            kv_dtype=e.get("kv_dtype"))
    with spans.span("setup.warm_programs"):
        eng.warm_programs()
        jax.block_until_ready(eng.pool.pools)
    return model, eng


class ClosedLoopDriver:
    def __init__(self, eng, gen, spans, clock=time.perf_counter):
        self.eng, self.gen, self.clock = eng, gen, clock
        self.spans = spans
        self.live: dict[str, Req] = {}
        self.done: list[Req] = []
        self.all: list[Req] = []
        self.steps: list[Step] = []
        self.failed = 0
        self._next_k = [0] * gen.clients
        self._idle = list(range(gen.clients))

    def submit_idle(self):
        for c in self._idle:
            k = self._next_k[c]
            self._next_k[c] += 1
            prompt, n_out = self.gen.request(c, k)
            r = Req(c, k, prompt, n_out, self.clock())
            self.all.append(r)
            try:
                r.rid = self.eng.add_request(prompt, n_out)
            except Exception as exc:   # a refusal is a failed request
                r.reason = f"refused: {type(exc).__name__}"
                self.failed += 1
                continue
            self.live[r.rid] = r
        self._idle = []

    def step(self):
        """One engine step and the harness's record of it."""
        before = {rid: (r.ctx, len(r.tokens)) for rid, r in self.live.items()}
        t0 = self.clock()
        with self.spans.span("bench.engine_step"):
            events = self.eng.step()
        t1 = self.clock()
        with self.spans.span("bench.record"):
            for ev in events:
                r = self.live.get(ev["rid"])
                if r is None:
                    continue
                if ev["token"] is not None:
                    r.tokens.append(int(ev["token"]))
                    r.t_tokens.append(t1)
                if ev["finished"]:
                    r.reason = ev["finish_reason"]
            rows = keys = 0
            mixed = False
            dec = []
            for rid, (c0, n0) in before.items():
                r = self.live[rid]
                if r.reason is None:
                    r.ctx = int(self.eng.request(rid).context_len)
                    c1 = r.ctx
                else:            # finished in this step: one decode row,
                    c1 = c0 + 1  # or its last chunk (prompt fully cached)
                    if n0 == 0:
                        c1 = len(r.prompt)
                if c1 <= c0:
                    continue
                rows += c1 - c0
                keys += (c1 * (c1 + 1) - c0 * (c0 + 1)) // 2
                if n0 == 0:
                    mixed = True   # a request with no token yet advanced:
                else:              # a prefill chunk was in this step
                    dec.append(c1)
            live = tuple(r.ctx for r in self.live.values()
                         if r.reason is None)
            self.steps.append(Step(t0, t1, mixed, rows, keys, tuple(dec),
                                   live))
            for rid in [rid for rid, r in self.live.items()
                        if r.reason is not None]:
                r = self.live.pop(rid)
                if r.reason != "length" or len(r.tokens) != r.n_out:
                    self.failed += 1
                self.done.append(r)
                self._idle.append(r.client)

    def all_decoding(self) -> bool:
        return (not self._idle
                and all(r.tokens for r in self.live.values()))


def run(cfg, spec, seed, seconds, family, tracer, spans):
    """Set-up, the window, the close. Returns the harness's record."""
    import jax

    gen = make_traffic(spec, seed, cfg["vocab_size"])
    model, eng = build_engine(cfg, seed, family, gen.max_tokens, spans)
    drv = ClosedLoopDriver(eng, gen, spans)
    with spans.span("setup.start_clients"):
        guard = 0
        while True:
            drv.submit_idle()
            if drv.all_decoding():
                break
            drv.step()
            guard += 1
            if guard > 10_000:
                raise RuntimeError("set-up: the clients never all decoded")
    counts0 = eng.step_program_counts()
    n_setup_steps = len(drv.steps)
    # what set-up and the run before wrote (the compile cache) goes to disk
    # now: its write-back inside the window stalled single steps for
    # seconds in 5 of 11 runs from a fresh checkout (PERF.md, PR 26)
    os.sync()
    tracer.start()
    t_open = drv.clock()
    t_close = t_open + seconds
    while drv.clock() < t_close:
        drv.submit_idle()
        drv.step()
    t_last = drv.steps[-1].t1
    tracer.stop()
    counts1 = eng.step_program_counts()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    record = {
        "t_open": t_open, "t_close": t_close, "t_last": t_last,
        "steps": drv.steps[n_setup_steps:], "requests": drv.all,
        "done": drv.done, "failed": drv.failed,
        "compiled_in_window": counts1 != counts0,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
        "page_size": cfg["engine"]["page_size"],
    }
    # free the program's state before the reference runs
    del drv.eng, drv, eng, model
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return record


def pctl(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(rec) -> tuple[dict, dict]:
    """The cell's end-to-end metrics from the record (``--trace 0``), each
    (value, unit), and the counts they were taken over. The window runs
    from its opening to the end of the step in flight when its seconds
    were up (``t_last``): every step begun inside it counts whole, its
    tokens and its time, so that no run loses the better part of a
    half-second step to where the clock happened to stop."""
    t0, t1 = rec["t_open"], rec["t_last"]
    seconds = t1 - t0
    n_tokens, gaps, ttfts = 0, [], []
    for r in rec["requests"]:
        for i, t in enumerate(r.t_tokens):
            if not (t0 <= t <= t1):
                continue
            n_tokens += 1
            if i == 0:
                ttfts.append(t - r.t_submit)
            else:
                gaps.append(t - r.t_tokens[i - 1])
    out = {"serve_out_tokens_per_s": (n_tokens / seconds, "tokens/s")}
    if gaps:
        out["itl_p95_ms"] = (1e3 * pctl(gaps, 95), "ms")
    if ttfts:
        out["ttft_p95_ms"] = (1e3 * pctl(ttfts, 95), "ms")
    pages = max((sum(-(-c // rec["page_size"]) for c in s.live_contexts)
                 for s in rec["steps"]), default=0)
    decode = [s.t1 - s.t0 for s in rec["steps"] if not s.mixed]
    mixed = [s.t1 - s.t0 for s in rec["steps"] if s.mixed]
    return out, {"tokens": n_tokens, "gaps": len(gaps), "ttfts": len(ttfts),
                 "window_s": seconds, "live_pages_peak": pages,
                 "decode_steps": len(decode), "decode_s": sum(decode),
                 "longest_decode_step_s": max(decode, default=0.0),
                 "mixed_steps": len(mixed), "mixed_s": sum(mixed),
                 "longest_mixed_step_s": max(mixed, default=0.0)}


def check_sample(rec, spec, seed):
    """The requests whose served tokens the reference follows: every
    request that finished inside the window. Only where there are more
    than the mix's ``check_requests``: the longest, then the clients in
    turn, each giving its finished requests in an order drawn from the
    seed, until that many are taken (so every client is followed)."""
    t0, t1 = rec["t_open"], rec["t_last"]
    fin = [r for r in rec["done"]
           if r.reason == "length" and r.t_tokens
           and t0 <= r.t_tokens[-1] <= t1]
    cap = int(spec["check_requests"])
    if len(fin) <= cap:
        return fin
    fin.sort(key=lambda r: (r.client, r.k))
    longest = max(fin, key=lambda r: len(r.prompt) + len(r.tokens))
    rng = np.random.default_rng([int(seed), 4])
    by_client: dict[int, list] = {}
    for i in rng.permutation(len(fin)):
        if fin[i] is not longest:
            by_client.setdefault(fin[i].client, []).append(fin[i])
    pick = [longest]
    while len(pick) < cap:
        for c in sorted(by_client):
            if by_client[c] and len(pick) < cap:
                pick.append(by_client[c].pop())
    return pick


def attempted(rec) -> int:
    return len(rec["requests"])


def check(rec, cfg, spec, seed, control=False):
    from benchmarks import check as compare

    sample = check_sample(rec, spec, seed)
    return compare.serve(cfg, seed, sample, control)
