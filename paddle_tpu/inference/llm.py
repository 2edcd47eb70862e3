"""LLM serving front-end (the Predictor analogue for generative decode).

``create_predictor`` serves fixed-shape programs; LLM serving is the
opposite regime — ragged prompts arriving over time, each wanting its
own decode length and sampling. ``LLMPredictor`` closes that gap by
fronting the continuous-batching engine in ``paddle_tpu.serving``: a
Predictor-shaped object (create → feed → fetch) whose ``generate`` runs
every prompt through one paged KV pool with iteration-level scheduling,
and whose ``stream`` exposes tokens as they decode.
"""

from __future__ import annotations

import numpy as np

__all__ = ["LLMPredictor", "create_llm_predictor"]


class LLMPredictor:
    """Batch-of-prompts front door over ``serving.ServingEngine``.

    Unlike ``generate()`` on the model (one fixed-shape batch, padded to
    the longest prompt), requests here share the paged pool: no padding
    waste, arrivals can be staggered, and greedy outputs are bitwise
    identical to per-request ``model.generate`` (SERVING.md).

    ``prefill_token_budget`` (the prompt tokens one step may prefill) is
    handed to the engine as given; ``None`` lets the engine derive it
    from the model and ``max_slots`` (SERVING.md "Budget metering").
    """

    def __init__(self, model, num_pages: int = 128, page_size: int = 16,
                 max_slots: int = 8, max_pages_per_slot: int | None = None,
                 prefill_token_budget: int | None = None, kv_dtype=None,
                 clock=None, max_queue_depth: int | None = None,
                 max_preemptions: int | None = None,
                 step_timeout_s: float | None = None,
                 drain_timeout_s: float | None = 30.0,
                 prefix_cache: bool = True, kv_quant: bool = False,
                 weight_quant: bool = False):
        from ..serving import ServingEngine
        if weight_quant:
            # int8 weight streaming (SERVING.md "Quantized KV & weights"):
            # decode matmuls stream int8 codes + per-channel scales and
            # dequantize in the matmul epilogue — ~half the weight bytes
            # of bf16 per decode step
            from ..quantization.serving import quantize_for_serving
            model = quantize_for_serving(model)
        self.model = model
        self._mk = lambda: ServingEngine(
            model, num_pages=num_pages, page_size=page_size,
            max_slots=max_slots, max_pages_per_slot=max_pages_per_slot,
            prefill_token_budget=prefill_token_budget, kv_dtype=kv_dtype,
            clock=clock, max_queue_depth=max_queue_depth,
            max_preemptions=max_preemptions, step_timeout_s=step_timeout_s,
            drain_timeout_s=drain_timeout_s, prefix_cache=prefix_cache,
            kv_quant=kv_quant)
        self.engine = self._mk()

    #: typed serving error -> the stable ``error`` string reported by
    #: :meth:`generate_detailed` (documented in SERVING.md "Serving
    #: failure modes"; the set is append-only — callers may switch on it)
    FAILURE_CODES = {
        "QueueFullError": "queue_full",
        "RequestTooLargeError": "too_large",
        "EngineDrainingError": "draining",
        "SchedulerStalledError": "scheduler_stalled",
        "FleetOverloadedError": "overloaded",
    }

    def generate(self, prompts, max_new_tokens: int = 32,
                 eos_token_id: int | None = None, sampling=None,
                 max_steps: int | None = None):
        """Run a batch of ragged prompts to completion; returns a list of
        generated-token lists in prompt order. ``sampling`` is one
        SamplingParams for all, or a per-prompt list. Raises the typed
        serving errors (QueueFullError / RequestTooLargeError /
        EngineDrainingError / SchedulerStalledError) — use
        :meth:`generate_detailed` for per-prompt failure results
        instead of exceptions."""
        if sampling is not None and isinstance(sampling, (list, tuple)):
            if len(sampling) != len(prompts):
                raise ValueError(
                    f"{len(sampling)} sampling params for "
                    f"{len(prompts)} prompts")
            per = list(sampling)
        else:
            per = [sampling] * len(prompts)
        rids = [self.engine.add_request(np.asarray(p).reshape(-1),
                                        max_new_tokens, sampling=sp,
                                        eos_token_id=eos_token_id)
                for p, sp in zip(prompts, per)]
        results = self.engine.run_to_completion(max_steps=max_steps)
        return [results[rid] for rid in rids]

    def generate_detailed(self, prompts, max_new_tokens: int = 32,
                          eos_token_id: int | None = None, sampling=None,
                          deadline_s: float | None = None,
                          max_queue_wait_s: float | None = None,
                          max_steps: int | None = None):
        """Like :meth:`generate`, but every typed serving failure becomes
        a stable per-prompt result instead of an exception. Returns one
        dict per prompt, in order:

        ``{"tokens": [...], "finish_reason": str | None, "error":
        None | "queue_full" | "too_large" | "draining" |
        "scheduler_stalled" | "overloaded", "retryable": bool}``

        Rejected prompts carry ``finish_reason="rejected"`` and empty
        tokens; accepted prompts carry the engine's classified
        finish_reason (``stop`` / ``length`` / ``timeout`` /
        ``nonfinite`` / ``preempted`` / ``preempted_limit`` /
        ``injected`` — SERVING.md). A scheduler stall marks every
        still-unfinished prompt ``scheduler_stalled`` rather than
        raising. ``retryable`` surfaces the typed error's own
        ``ServingError.retryable`` flag, so a transient shed
        (queue_full / draining / overloaded — back off and resubmit,
        possibly elsewhere) is machine-distinguishable from a terminal
        rejection (too_large: every homogeneous replica refuses it
        identically, retrying is futile)."""
        from ..serving import SchedulerStalledError, ServingError
        if sampling is not None and isinstance(sampling, (list, tuple)):
            per = list(sampling)
        else:
            per = [sampling] * len(prompts)
        outcomes = [None] * len(prompts)
        rids: dict[str, int] = {}
        for i, (p, sp) in enumerate(zip(prompts, per)):
            try:
                rid = self.engine.add_request(
                    np.asarray(p).reshape(-1), max_new_tokens, sampling=sp,
                    eos_token_id=eos_token_id, deadline_s=deadline_s,
                    max_queue_wait_s=max_queue_wait_s)
                rids[rid] = i
            except ServingError as e:
                outcomes[i] = {"tokens": [], "finish_reason": "rejected",
                               "error": self.FAILURE_CODES.get(
                                   type(e).__name__, "serving_error"),
                               "retryable": bool(e.retryable)}
        stalled = False
        try:
            self.engine.run_to_completion(max_steps=max_steps)
        except SchedulerStalledError:
            stalled = True
        for rid, i in rids.items():
            req = self.engine.request(rid)
            if req.finish_reason is None:
                # SchedulerStalledError.retryable is True: a stall is
                # an engine-side livelock, not the request's fault
                outcomes[i] = {"tokens": list(req.tokens),
                               "finish_reason": "stalled" if stalled
                               else None,
                               "error": "scheduler_stalled" if stalled
                               else None,
                               "retryable": stalled}
            else:
                outcomes[i] = {"tokens": list(req.tokens),
                               "finish_reason": req.finish_reason,
                               "error": None,
                               # matches drain()'s retriable contract:
                               # only a preempted eviction computed
                               # nothing the client is owed elsewhere
                               "retryable": req.finish_reason
                               == "preempted"}
        return outcomes

    def drain(self, timeout_s: float | None = None) -> dict:
        """Graceful shutdown passthrough: ``engine.drain`` — stops
        admission and reports per-request outcomes (SERVING.md)."""
        return self.engine.drain(timeout_s=timeout_s)

    def stream(self, prompts, max_new_tokens: int = 32,
               eos_token_id: int | None = None, sampling=None):
        """Token-at-a-time iterator: yields ``{"index", "rid", "token",
        "finished", "finish_reason"}`` with ``index`` the prompt's
        position in the input batch."""
        rids = [self.engine.add_request(np.asarray(p).reshape(-1),
                                        max_new_tokens, sampling=sampling,
                                        eos_token_id=eos_token_id)
                for p in prompts]
        pos = {rid: i for i, rid in enumerate(rids)}
        for ev in self.engine.stream():
            if ev["rid"] in pos:
                yield {"index": pos[ev["rid"]], **ev}

    def metrics_summary(self) -> dict:
        """Engine metrics incl. the prefix-cache view: ``cache_hit_rate``
        (fraction of prefill context tokens served from cached pages)
        plus the pool's lookup/hit/eviction/COW counters (SERVING.md
        "Prefix caching")."""
        return self.engine.metrics.summary()

    def stats(self) -> dict:
        return self.engine.stats()

    def reset(self) -> None:
        """Fresh engine: drops metrics and the request table. Prefer one
        long-lived predictor — a new engine builds a new decode program."""
        self.engine = self._mk()


def create_llm_predictor(model, **kw) -> LLMPredictor:
    return LLMPredictor(model, **kw)
