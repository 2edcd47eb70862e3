"""Deterministic serving traffic generator (ROADMAP item 5).

The staggered synthetic traces the bench configs used until now ("2 at
t=0, then 1 every 4 steps") cannot produce the regimes that actually
rank schedulers, cache tiers and admission policies: arrival bursts
that overflow the queue, Zipf-skewed tenant popularity that makes some
prefixes hot and others cold, and mixed prompt lengths that fragment
the pool. This module builds those traces as replayable data:

- **Arrivals** are counted per engine step (not wall seconds — the
  engine's only deterministic timebase) from a seeded generator:
  ``poisson`` draws a constant-rate Poisson count per step; ``bursty``
  modulates the rate with a deterministic on/off square wave (a
  Markov-modulated Poisson process with fixed phase lengths), the
  arrival shape that stresses queue depth and preemption.
- **Prompts** are ``system prefix + user suffix``: each request picks a
  tenant from a Zipf-popularity distribution over ``tenants`` distinct
  system prompts (tenant 0 hottest), then appends a fresh random suffix
  whose length is drawn from a weighted mixture of ranges. Shared
  system prompts are exactly what the prefix cache and the host tier
  monetize; the Zipf skew decides which of them stay warm.
- **Replay** is a pure function of the built trace: ``replay(target)``
  drives a :class:`~paddle_tpu.serving.engine.ServingEngine` or a
  :class:`~paddle_tpu.serving.fleet.FleetRouter` (duck-typed on
  ``submit``/``add_request``) step by step, submitting each request at
  its arrival step. Same ``Workload`` + same engine seed => bitwise
  identical streams, so A/B arms (tier off vs on) see IDENTICAL
  traffic and their goodput_at_slo / hit-rate deltas are attributable
  to the thing under test alone.

Everything derives from ``numpy.random.default_rng(seed)`` — no global
RNG state, no wall clock — so a Workload is a value: build it once,
replay it on every arm, ship its ``stats()`` in the bench summary.
"""

from __future__ import annotations

import bisect
import inspect
import math
from dataclasses import dataclass, field

import numpy as np

__all__ = ["Workload", "WorkloadRequest", "WorkloadSpec",
           "heavy_tail_workload", "long_prompt_workload", "make_workload",
           "overload_workload"]


@dataclass
class WorkloadRequest:
    """One trace entry: submit ``prompt`` at engine step
    ``arrival_step`` asking for ``max_new_tokens``. ``priority``
    (larger = more important) and ``deadline_s`` express the request's
    SLO class (SERVING.md "Overload control & tenant fairness") —
    replay forwards them to targets that accept them."""
    rid: str
    arrival_step: int
    prompt: list[int]
    max_new_tokens: int
    tenant: int
    priority: int = 0
    deadline_s: float | None = None


@dataclass
class WorkloadSpec:
    """Knobs for :func:`make_workload` (SERVING.md "KV tiering &
    traffic harness" documents each one).

    ``arrival`` is "poisson" or "bursty"; ``rate`` is mean arrivals per
    engine step. Bursty traffic alternates ``burst_on``-step windows at
    ``rate * burst_factor`` with ``burst_off``-step windows at
    ``rate * idle_factor``. ``prompt_mix`` is a weighted mixture of
    inclusive user-suffix length ranges; ``system_len`` is the range of
    per-tenant system-prompt lengths; ``zipf_alpha`` skews tenant
    popularity (tenant 0 hottest; larger alpha = hotter head)."""
    seed: int = 0
    n_requests: int = 32
    arrival: str = "poisson"
    rate: float = 0.5
    burst_on: int = 8
    burst_off: int = 24
    burst_factor: float = 4.0
    idle_factor: float = 0.0
    tenants: int = 4
    zipf_alpha: float = 1.2
    system_len: tuple[int, int] = (32, 64)
    prompt_mix: tuple = ((0.6, 8, 24), (0.3, 24, 64), (0.1, 64, 128))
    max_new: tuple[int, int] = (8, 32)
    vocab_size: int = 256
    eos_token_id: int | None = None
    # heavy-tailed suffix lengths (the chunked-prefill regime): with
    # ``suffix_dist="lognormal"``, a ``heavy_frac`` coin decides per
    # request between a LONG prompt — suffix length drawn from
    # lognormal(mu, sigma), clipped to ``suffix_clip`` — and the short
    # ``prompt_mix`` draw. Short requests optionally get their own
    # decode-heavy ``light_max_new`` range, so the trace interleaves
    # rare huge prefills with a steady stream of decode traffic —
    # exactly the mix where whole-prompt prefill stalls decode ITL.
    suffix_dist: str = "mixture"
    heavy_frac: float = 0.3
    lognormal_mu: float = 4.2
    lognormal_sigma: float = 0.8
    suffix_clip: tuple[int, int] = (48, 320)
    light_max_new: tuple[int, int] | None = None
    # SLO classes (the overload-control regime): per-tenant priority
    # (one int per tenant, larger = more important) and per-tenant
    # deadline distribution — each entry is None (no deadline), a
    # scalar seconds value, or an inclusive (lo, hi) uniform range
    # drawn per request. Both default off, so every existing trace
    # stays bitwise identical. Tenant 0 hot + LOW priority is the
    # canonical overload trace (:func:`overload_workload`).
    tenant_priorities: tuple | None = None
    tenant_deadlines: tuple | None = None


class Workload:
    """A built, replayable arrival trace (requests sorted by arrival
    step, FCFS within a step)."""

    def __init__(self, requests: list[WorkloadRequest],
                 spec: WorkloadSpec | None = None,
                 system_prompts: list[list[int]] | None = None):
        self.requests = sorted(requests,
                               key=lambda r: (r.arrival_step, r.rid))
        self.spec = spec
        self.system_prompts = system_prompts or []

    def __len__(self) -> int:
        return len(self.requests)

    def __iter__(self):
        return iter(self.requests)

    @property
    def horizon(self) -> int:
        """Last arrival step (replay keeps stepping past it until the
        target drains)."""
        return self.requests[-1].arrival_step if self.requests else 0

    def due(self, step: int) -> list[WorkloadRequest]:
        """Requests arriving exactly at ``step`` (pure — no cursor, so
        one Workload can drive any number of A/B arms)."""
        return [r for r in self.requests if r.arrival_step == step]

    def stats(self) -> dict:
        """Shape summary for bench reports: determinism means these
        describe every replay of this trace."""
        if not self.requests:
            return {"n_requests": 0}
        plens = [len(r.prompt) for r in self.requests]
        per_tenant: dict[int, int] = {}
        for r in self.requests:
            per_tenant[r.tenant] = per_tenant.get(r.tenant, 0) + 1
        return {
            "n_requests": len(self.requests),
            "arrival_span_steps": self.horizon + 1,
            "prompt_len_min": min(plens),
            "prompt_len_mean": sum(plens) / len(plens),
            "prompt_len_max": max(plens),
            "tenants": len(self.system_prompts),
            "tenant_counts": [per_tenant.get(t, 0)
                              for t in range(len(self.system_prompts))],
            "max_new_total": sum(r.max_new_tokens for r in self.requests),
        }

    def replay(self, target, max_steps: int | None = None,
               rid_prefix: str = "", retry_sheds: bool = True) -> dict:
        """Drive ``target`` (engine or fleet router) through the trace:
        at each step, submit the requests due, then ``target.step()``;
        keep stepping until the target drains. Backpressure rejections
        (typed ServingError subclasses with ``retryable`` set) are
        retried ONCE, deterministically: the request re-enqueues at
        ``step + max(1, ceil(retry_after_s))`` (1 when the error
        carries no hint), honouring the backoff the engine computed —
        so lossy-transport benches measure goodput, not shed luck. A
        request rejected again on its retry (or non-retryably) counts
        as shed, not raised — a traffic harness measures load shedding,
        it doesn't crash on it. ``retry_sheds=False`` restores the
        drop-on-first-shed behaviour. Returns ``{"steps", "submitted",
        "shed", "retried", "rids"}``."""
        from .errors import ServingError
        submit = getattr(target, "submit", None) or target.add_request
        has_work = (getattr(target, "has_work", None)
                    or target.scheduler.has_work)
        eos = self.spec.eos_token_id if self.spec is not None else None
        # forward tenant/priority/deadline_s only to targets whose
        # submit accepts them (signature probe, computed once) — a
        # scripted replay target without tenancy keeps working
        try:
            params = inspect.signature(submit).parameters
            slo_aware = ("tenant" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values()))
        except (TypeError, ValueError):
            slo_aware = False
        i, step, shed, retried = 0, 0, 0, 0
        rids: list[str] = []
        deferred: list[tuple[int, object]] = []   # (due step, request)
        n = len(self.requests)

        def _submit_one(r, is_retry: bool) -> None:
            nonlocal shed, retried
            kw: dict = {}
            if slo_aware:
                kw["tenant"] = r.tenant
                kw["priority"] = r.priority
                if r.deadline_s is not None:
                    kw["deadline_s"] = r.deadline_s
            try:
                rids.append(submit(r.prompt, r.max_new_tokens,
                                   eos_token_id=eos,
                                   rid=rid_prefix + r.rid, **kw))
            except ServingError as e:
                if retry_sheds and not is_retry and e.retryable:
                    # single deterministic re-enqueue honouring the
                    # engine's own backoff hint (retry_after_s rides
                    # FleetOverloadedError / AdmissionShedError; errors
                    # without one wait the minimum one step)
                    hint = getattr(e, "retry_after_s", None) or 0.0
                    delay = max(1, math.ceil(hint))
                    bisect.insort(deferred, (step + delay, id(r), r))
                    retried += 1
                else:
                    shed += 1

        while i < n or deferred or has_work():
            while i < n and self.requests[i].arrival_step <= step:
                r = self.requests[i]
                i += 1
                _submit_one(r, is_retry=False)
            while deferred and deferred[0][0] <= step:
                _, _, r = deferred.pop(0)
                _submit_one(r, is_retry=True)
            target.step()
            step += 1
            if max_steps is not None and step >= max_steps:
                raise RuntimeError(
                    f"workload replay did not drain in {step} steps "
                    f"({n - i} unsubmitted, target still busy)")
        return {"steps": step, "submitted": len(rids), "shed": shed,
                "retried": retried, "rids": rids}


def _arrival_steps(spec: WorkloadSpec, rng) -> list[int]:
    """Per-step Poisson arrival counts, optionally rate-modulated by
    the deterministic on/off burst wave, until n_requests are placed."""
    steps: list[int] = []
    step = 0
    period = spec.burst_on + spec.burst_off
    while len(steps) < spec.n_requests:
        rate = spec.rate
        if spec.arrival == "bursty":
            in_burst = (step % period) < spec.burst_on
            rate = spec.rate * (spec.burst_factor if in_burst
                                else spec.idle_factor)
        k = int(rng.poisson(rate))
        for _ in range(min(k, spec.n_requests - len(steps))):
            steps.append(step)
        step += 1
        if step > 1000 * (spec.n_requests + 1):
            raise ValueError(
                f"arrival rate too low to place {spec.n_requests} "
                f"requests (arrival={spec.arrival!r}, rate={spec.rate}, "
                f"idle_factor={spec.idle_factor})")
    return steps


def make_workload(spec: WorkloadSpec | None = None, **kw) -> Workload:
    """Build a :class:`Workload` from a spec (or spec fields as
    kwargs). Fully deterministic in ``spec.seed``."""
    if spec is None:
        spec = WorkloadSpec(**kw)
    elif kw:
        raise TypeError("pass a WorkloadSpec OR field kwargs, not both")
    if spec.arrival not in ("poisson", "bursty"):
        raise ValueError(f"unknown arrival process {spec.arrival!r}")
    if spec.suffix_dist not in ("mixture", "lognormal"):
        raise ValueError(f"unknown suffix_dist {spec.suffix_dist!r}")
    if spec.tenants < 1:
        raise ValueError("tenants must be >= 1")
    if (spec.tenant_priorities is not None
            and len(spec.tenant_priorities) != spec.tenants):
        raise ValueError(
            f"tenant_priorities needs one entry per tenant "
            f"({len(spec.tenant_priorities)} != {spec.tenants})")
    if (spec.tenant_deadlines is not None
            and len(spec.tenant_deadlines) != spec.tenants):
        raise ValueError(
            f"tenant_deadlines needs one entry per tenant "
            f"({len(spec.tenant_deadlines)} != {spec.tenants})")
    rng = np.random.default_rng(spec.seed)
    # per-tenant system prompts (the shared prefixes): lengths first,
    # then token draws, all from the one seeded stream
    system_prompts: list[list[int]] = []
    for _ in range(spec.tenants):
        n = int(rng.integers(spec.system_len[0], spec.system_len[1] + 1))
        system_prompts.append(
            [int(t) for t in rng.integers(0, spec.vocab_size, size=n)])
    # Zipf tenant popularity: p(rank) ~ 1/(rank+1)^alpha, tenant 0 hottest
    ranks = np.arange(1, spec.tenants + 1, dtype=np.float64)
    probs = ranks ** -spec.zipf_alpha
    probs /= probs.sum()
    weights = np.asarray([w for w, _, _ in spec.prompt_mix], np.float64)
    weights /= weights.sum()
    arrivals = _arrival_steps(spec, rng)
    requests: list[WorkloadRequest] = []
    for i, arrival in enumerate(arrivals):
        tenant = int(rng.choice(spec.tenants, p=probs))
        heavy = (spec.suffix_dist == "lognormal"
                 and bool(rng.random() < spec.heavy_frac))
        if heavy:
            lo, hi = spec.suffix_clip
            sfx_len = int(np.clip(
                round(rng.lognormal(spec.lognormal_mu,
                                    spec.lognormal_sigma)), lo, hi))
        else:
            bucket = int(rng.choice(len(weights), p=weights))
            _, lo, hi = spec.prompt_mix[bucket]
            sfx_len = int(rng.integers(lo, hi + 1))
        suffix = [int(t) for t in rng.integers(0, spec.vocab_size,
                                               size=sfx_len)]
        mn = (spec.light_max_new
              if not heavy and spec.light_max_new is not None
              else spec.max_new)
        max_new = int(rng.integers(mn[0], mn[1] + 1))
        # SLO class: priority is a pure per-tenant lookup (no draw);
        # a deadline draw happens ONLY for tenants that have one, so
        # traces without SLO classes keep their exact draw order
        priority = (int(spec.tenant_priorities[tenant])
                    if spec.tenant_priorities is not None else 0)
        deadline: float | None = None
        if spec.tenant_deadlines is not None:
            d = spec.tenant_deadlines[tenant]
            if d is not None:
                try:
                    lo_d, hi_d = d
                    deadline = float(rng.uniform(lo_d, hi_d))
                except TypeError:
                    deadline = float(d)
        requests.append(WorkloadRequest(
            rid=f"wl-{i:04d}", arrival_step=arrival,
            prompt=system_prompts[tenant] + suffix,
            max_new_tokens=max_new, tenant=tenant,
            priority=priority, deadline_s=deadline))
    return Workload(requests, spec=spec, system_prompts=system_prompts)


def heavy_tail_workload(seed: int = 0, n_requests: int = 24,
                        **overrides) -> Workload:
    """The chunked-prefill stress preset: lognormal long prompts
    (~30% of requests, suffixes up to a few hundred tokens) interleaved
    with short decode-heavy traffic on small shared system prompts.
    Without chunking, each long prompt monopolizes an entire step and
    every decoding slot's inter-token latency eats the full prefill;
    with chunking the prompt streams through in budget-sized bites
    (``tests/test_serving_chunked.py::TestHeavyTailWorkload`` replays
    it). Deterministic in ``seed``; any :class:`WorkloadSpec` field can be overridden."""
    kw: dict = dict(seed=seed, n_requests=n_requests,
                    arrival="poisson", rate=0.75,
                    tenants=2, zipf_alpha=1.2, system_len=(8, 16),
                    suffix_dist="lognormal", heavy_frac=0.3,
                    lognormal_mu=4.2, lognormal_sigma=0.8,
                    suffix_clip=(48, 320),
                    prompt_mix=((1.0, 4, 12),),
                    max_new=(4, 8), light_max_new=(16, 48))
    kw.update(overrides)
    return make_workload(WorkloadSpec(**kw))


def long_prompt_workload(seed: int = 0, n_requests: int = 16,
                         prompt_scale: float = 1.0,
                         **overrides) -> Workload:
    """The disaggregated-serving trace (ROADMAP item 1, SERVING.md
    "Disaggregated serving"): long-prompt-HEAVY Poisson arrivals over
    Zipf-shared system prompts — a lognormal prompt-length mixture
    where most requests (~70%) carry a LONG prompt and every request
    decodes a modest stream, the regime where prefill and decode fight
    hardest for the per-step budget even under chunking.
    ``prompt_scale`` is the 10x knob: it shifts the lognormal mu by
    ``ln(prompt_scale)`` and scales the clip range, so
    ``prompt_scale=10`` makes the same trace's prompts ~10x longer
    while arrivals, tenants and decode lengths stay fixed: the knob
    a colocated-against-disaggregated comparison sweeps (no benchmark
    cell does yet; speed not measured). Deterministic in ``seed``; any
    :class:`WorkloadSpec` field can be overridden."""
    scale = float(prompt_scale)
    if scale <= 0.0:
        raise ValueError(f"prompt_scale must be > 0, got {prompt_scale}")
    kw: dict = dict(seed=seed, n_requests=n_requests,
                    arrival="poisson", rate=0.5,
                    tenants=2, zipf_alpha=1.2, system_len=(8, 16),
                    suffix_dist="lognormal", heavy_frac=0.7,
                    lognormal_mu=3.3 + math.log(scale),
                    lognormal_sigma=0.6,
                    suffix_clip=(max(8, int(round(16 * scale))),
                                 max(16, int(round(160 * scale)))),
                    prompt_mix=((1.0, 4, 12),),
                    max_new=(6, 12), light_max_new=(8, 16))
    kw.update(overrides)
    return make_workload(WorkloadSpec(**kw))


def overload_workload(seed: int = 0, n_requests: int = 48,
                      **overrides) -> Workload:
    """The canonical hot-tenant overload preset (SERVING.md "Overload
    control & tenant fairness"): tenant 0 is HOT (steep Zipf head,
    ~2/3 of all traffic) and LOW priority — the batch scraper flooding
    a shared fleet — while the cold tenants carry higher priorities,
    i.e. the interactive SLO classes a brownout must protect. Bursty
    arrivals overflow the queue during on-phases so admission quotas,
    fair scheduling and the brownout ladder all engage; FCFS collapses
    the cold tenants' TTFT on this trace, which is exactly what
    ``tests/test_serving_fairness.py::TestOverloadAcceptance`` compares
    against fairness with brownout. Deadlines default OFF
    (pass ``tenant_deadlines=...`` to exercise infeasibility shedding
    on a virtual clock). Deterministic in ``seed``; any
    :class:`WorkloadSpec` field can be overridden."""
    kw: dict = dict(seed=seed, n_requests=n_requests,
                    arrival="bursty", rate=1.25,
                    burst_on=6, burst_off=10,
                    burst_factor=4.0, idle_factor=0.25,
                    tenants=4, zipf_alpha=2.5, system_len=(12, 20),
                    prompt_mix=((0.5, 8, 24), (0.35, 24, 64),
                                (0.15, 64, 96)),
                    max_new=(6, 16),
                    tenant_priorities=(0, 2, 2, 3))
    kw.update(overrides)
    return make_workload(WorkloadSpec(**kw))
