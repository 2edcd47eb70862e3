"""Serving metrics: per-request latency percentiles and engine gauges.

Tracks the three latencies the serving literature reports —
- TTFT  (time to first token): arrival -> first token emitted;
- TPOT  (time per output token): (last_token_t - first_token_t) / (n-1);
- ITL   (inter-token latency): each consecutive token gap —
plus queue-depth and KV-pool-utilization gauges sampled once per engine
step, queue-wait percentiles (arrival -> first admission), and the
failure-outcome counters of the robustness layer (rejects, timeouts,
quarantines, preemption-limit kills, drain evictions — see the
"Serving failure modes" table in SERVING.md). The clock is injectable
so tests can feed a deterministic virtual
time; deadline enforcement in the engine runs on this same clock, and a
``Tracer`` (paddle_tpu.observability) constructed on the same clock
puts spans and percentiles in one timebase.

``goodput_at_slo`` is the SLO view (ROADMAP item 5): requests/s that
finished normally AND met the TTFT / per-request-ITL-p99 SLOs — the
metric that ranks schedulers, cache tiers and admission policies
against each other, exported via ``summary()`` (``set_slo`` arms the
thresholds) and rendered by ``observability.render_prometheus``.
"""

from __future__ import annotations

import time

__all__ = ["ServingMetrics", "FleetMetrics", "percentile"]


def percentile(values, p: float) -> float:
    """Linearly-interpolated percentile (p in [0, 100]), numpy's default
    ``linear`` method: the rank ``p/100 * (n-1)`` is interpolated
    between its two neighbouring order statistics. 0.0 on empty input."""
    if not values:
        return 0.0
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    rank = (p / 100.0) * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


class FleetMetrics:
    """Counter bag for the fleet router (serving.fleet.FleetRouter) —
    the numbers SERVING.md "Engine fleet & failover" defines and
    ``observability.render_fleet_prometheus`` exports as
    ``paddle_serving_fleet_*_total``:

    - ``dispatched``        placements onto a replica (incl. replays)
    - ``failovers``         in-flight requests re-queued off a dead replica
    - ``replayed_requests`` re-dispatches that replay a prior stream
    - ``replayed_tokens``   replayed positions verified + suppressed
      (each one is a bitwise determinism check that passed)
    - ``shed``              FleetOverloadedError rejects + terminal sheds
    - ``ejections``         replicas marked DEAD
    - ``breaker_opens``     circuit-breaker CLOSED/HALF_OPEN -> OPEN edges
    - ``probes``            OPEN -> HALF_OPEN probe windows

    Bounded-replay failover (serving/snapshot.py; RESILIENCE.md
    "Serving recovery playbook") adds:

    - ``snapshot_restores``        failover placements seeded from a
      verified snapshot (bounded replay) instead of token 0
    - ``snapshot_fallbacks``       failover placements that wanted a
      snapshot but fell back to full replay (missing/corrupt/unusable)
    - ``recovery_restored_tokens`` tokens skipped by snapshot seeding
    - ``recovery_replayed_tokens`` delta tokens each failover still has
      to re-produce (emitted - seeded; the full-replay arm pays the
      whole emitted count here) — THE bounded-vs-full A/B number
    - ``recovery_ttfrt_p50_s`` / ``_p99_s`` (summary only): ejection ->
      first FRESH post-recovery token, via :meth:`observe_recovery`

    Partition-tolerant transport (serving/transport.py; SERVING.md
    "Fleet transport & membership") adds:

    - ``duplicates_suppressed``  result batches the router's per-replica
      seq dedup collapsed (at-least-once delivery made exactly-once)
    - ``stale_epoch_discarded``  messages from a zombie epoch (a replica
      back from a partition after ejection) counted and dropped — each
      one is the fence doing its job
    - ``lease_expirations``      replicas ejected because their
      heartbeat lease lapsed (no ack within ``lease_steps``)

    Disaggregated prefill/decode serving (``placement="disagg"``;
    SERVING.md "Disaggregated serving") adds the handoff ledger:

    - ``handoff_prefills``   requests whose prefill finished on a
      prefill-role replica (the KV now owes a handoff)
    - ``handoff_offers``     KV_OFFER messages the router received
    - ``handoff_bytes``      payload bytes carried by those offers
    - ``handoff_pulls``      KV_PULL placements that landed on a
      decode-role replica (includes re-pulls after a decode death)
    - ``handoff_commits``    KV_COMMIT releases sent back to the
      prefill replica (frees its held copy)
    - ``handoff_corrupt``    offered payloads the digest gate rejected
      (stripped on the wire, or refused at inject time)
    - ``handoff_timeouts``   offers that never became pullable within
      ``handoff_timeout_steps``
    - ``handoff_recomputes`` requests that fell back to a full
      colocated recompute (dropped/corrupt/timed-out/orphaned offer)
    - ``rerolls``            replica role flips (prefill <-> decode)
      under sustained queue-wait vs ITL pressure imbalance

    Client-visible latency/goodput lives on the router's own
    :class:`ServingMetrics`, not here — this bag is pure fleet-control
    accounting."""

    def __init__(self):
        self.counters: dict[str, int] = {
            "dispatched": 0, "failovers": 0, "replayed_requests": 0,
            "replayed_tokens": 0, "shed": 0, "ejections": 0,
            "breaker_opens": 0, "probes": 0,
            "snapshot_restores": 0, "snapshot_fallbacks": 0,
            "recovery_restored_tokens": 0, "recovery_replayed_tokens": 0,
            "duplicates_suppressed": 0, "stale_epoch_discarded": 0,
            "lease_expirations": 0,
            "handoff_prefills": 0, "handoff_offers": 0,
            "handoff_bytes": 0, "handoff_pulls": 0,
            "handoff_commits": 0, "handoff_corrupt": 0,
            "handoff_timeouts": 0, "handoff_recomputes": 0,
            "rerolls": 0,
        }
        # time-to-first-recovered-token samples: ejection -> the first
        # token beyond the request's pre-failover stream
        self.recovery_latency_s: list[float] = []

    def bump(self, key: str, n: int = 1) -> None:
        self.counters[key] += n

    def observe_recovery(self, dt: float) -> None:
        self.recovery_latency_s.append(float(dt))

    def summary(self) -> dict:
        return {**self.counters,
                "recovery_ttfrt_p50_s": percentile(
                    self.recovery_latency_s, 50),
                "recovery_ttfrt_p99_s": percentile(
                    self.recovery_latency_s, 99)}


class ServingMetrics:
    def __init__(self, clock=None):
        self._clock = clock if clock is not None else time.monotonic
        self._arrival: dict[str, float] = {}
        self._first_token: dict[str, float] = {}
        self._last_token: dict[str, float] = {}
        self._n_tokens: dict[str, int] = {}
        self._itl: list[float] = []
        self._itl_by_rid: dict[str, list[float]] = {}
        self._finish_reason: dict[str, str | None] = {}
        # SLO thresholds for goodput_at_slo in summary() (set_slo);
        # None = that dimension unconstrained
        self.slo_ttft_s: float | None = None
        self.slo_itl_s: float | None = None
        self._queue_depth: list[int] = []
        self._pool_util: list[float] = []
        self._finished = 0
        self._preemptions = 0
        self._start = None
        self._end = None
        self._admit_t: dict[str, float] = {}
        self._queue_wait: list[float] = []
        # disaggregated serving (SERVING.md "Disaggregated serving"):
        # per-request phase timestamps for ttft_breakdown() — when a
        # prefill-role replica finished the prompt (the KV handoff
        # starts) and when the pulled KV landed on a decode replica.
        # Colocated requests never touch these dicts, so their TTFT
        # attributes entirely to queue-wait + prefill-compute.
        self._prefill_done_t: dict[str, float] = {}
        self._handoff_admit_t: dict[str, float] = {}
        # failure-outcome counters (typed error surface, SERVING.md):
        # rejected_quota / rejected_infeasible are AdmissionShedError
        # sheds (tenant quota exhausted / deadline infeasible), "shed"
        # counts terminal shed outcomes (brownout level 3 + fleet)
        self.counters: dict[str, int] = {
            "rejected_queue_full": 0, "rejected_too_large": 0,
            "rejected_quota": 0, "rejected_infeasible": 0,
            "shed": 0,
            "timed_out": 0, "quarantined": 0, "preempted_limit": 0,
            "drained": 0, "injected": 0,
            # crash-consistent snapshots (serving/snapshot.py):
            # engine-side restore/save outcomes; the store's own
            # capture counters are mirrored in via on_snapshot_stats
            "snapshot_restores": 0, "snapshot_restored_tokens": 0,
            "snapshot_restore_failed": 0, "snapshot_restore_corrupt": 0,
            "snapshot_saves": 0,
            # disaggregated serving (engine side): finished-prefill KV
            # exports published to the handoff outbox
            "handoff_exports": 0,
        }
        # prefix-cache accounting (SERVING.md "Prefix caching"):
        # per-admission token totals accumulate here; the pool's page
        # counters (lookups/hits/evictions/COW) are mirrored in by the
        # engine each step
        self._prefill_tokens = 0
        self._prefill_cached_tokens = 0
        self._prefix_counters: dict[str, int] = {}
        # KV tiering (SERVING.md "KV tiering & traffic harness"):
        # restored tokens are the host-tier slice of the cached tokens
        # above (they skipped recompute but paid restore bytes); the
        # tier's byte gauges are mirrored in from HostTier.stats() each
        # step so summary()/render_prometheus carry spilled_bytes /
        # restored_bytes / host_pool_bytes without a second scrape
        self.host_tier_enabled = 0
        self._prefill_restored_tokens = 0
        self._tier_stats: dict[str, int] = {}
        # int8 KV-cache quantization (SERVING.md "Quantized KV & weights"):
        # the flag gauge plus a running max over per-prefill absmax scales —
        # scale_max/2 bounds the worst-case dequant error of any cached
        # element, the number an operator alerts on
        self.kv_quant_enabled = 0
        self.kv_quant_scale_max = 0.0
        # speculative decoding (SERVING.md "Speculative decoding"):
        # draft/accept token totals, drafter hit counts (calls that
        # proposed >= 1 token), and a per-draft-length accept histogram
        # {n_draft: [accepted_sum, verify_steps]} for the profiler's
        # accept-rate-by-length report
        self.spec_enabled = 0
        self._spec_draft_tokens = 0
        self._spec_accepted_tokens = 0
        self._spec_draft_calls = 0
        self._spec_draft_hits = 0
        self._spec_hist: dict[int, list[int]] = {}
        # chunked prefill / mixed steps (SERVING.md "Chunked prefill &
        # mixed steps"): per-step mixed-batch composition — how many
        # prefill-chunk tokens and decode slots shared each mixed
        # dispatch, how many chunks were cut in total, and how many
        # partially-prefilled requests were in flight at the last step.
        # Schema-stable zeros with chunking off.
        self.chunked_enabled = 0
        # crash-consistent snapshots (serving/snapshot.py): the flag
        # gauge plus a mirror of SnapshotStore.stats() refreshed at
        # each capture — schema-stable zeros with snapshots off
        self.snapshots_enabled = 0
        self._snapshot_stats: dict[str, int] = {}
        # multi-tenant LoRA serving (SERVING.md "Multi-tenant LoRA
        # serving"): the flag gauge plus a mirror of AdapterPool.stats()
        # refreshed each step — the lora_* keys become the
        # paddle_serving_lora_* Prometheus family; schema-stable zeros
        # with LoRA off
        self.lora_enabled = 0
        self._lora_stats: dict = {}
        # tensor parallelism (SERVING.md "Tensor-parallel serving"): the
        # TP degree gauge (1 == single-device engine) and the per-shard
        # KV footprint per cached token — the tp_* keys become the
        # paddle_serving_tp_* Prometheus family via render_prometheus
        self.tp_degree = 1
        self.tp_shard_kv_bytes_per_token = 0
        # pipeline parallelism (SERVING.md "Pipeline-parallel serving"):
        # the pp degree, mixed-step microbatch wave count, and the
        # schedule's idle-stage fraction — the pp_* keys become the
        # paddle_serving_pp_* Prometheus family; schema-stable
        # 1/1/0.0 on a non-pipelined engine
        self.pp_degree = 1
        self.pp_waves = 1
        self.pipeline_bubble_frac = 0.0
        self._mixed_steps = 0
        self._chunk_tokens = 0
        self._chunks_dispatched = 0
        self._chunk_prefill_tokens_last = 0
        self._chunk_decode_slots_last = 0
        self._chunks_in_flight_last = 0
        # SLO-aware overload control (SERVING.md "Overload control &
        # tenant fairness"): the fair/brownout flag gauges, the current
        # brownout level + per-level step occupancy + transition count,
        # and per-tenant / per-priority request attribution — tenants
        # and priorities arrive via on_arrival/on_shed, and summary()
        # flattens them to tenant{t}_* / shed_priority{p} keys so the
        # Prometheus page carries the per-tenant view for free
        self.fair_enabled = 0
        self.brownout_enabled = 0
        self._brownout_level = 0
        self._brownout_steps: dict[int, int] = {1: 0, 2: 0, 3: 0}
        self._brownout_transitions = 0
        self._tenant: dict[str, int] = {}
        self._priority: dict[str, int] = {}
        self._shed_by_priority: dict[int, int] = {}
        self._shed_by_tenant: dict[int, int] = {}

    def now(self) -> float:
        return self._clock()

    # ---- request lifecycle ----

    def on_arrival(self, rid: str, tenant: int = 0,
                   priority: int = 0) -> None:
        t = self.now()
        if self._start is None:
            self._start = t
        self._arrival[rid] = t
        self._tenant[rid] = int(tenant)
        self._priority[rid] = int(priority)

    def on_token(self, rid: str) -> None:
        t = self.now()
        if rid not in self._first_token:
            self._first_token[rid] = t
        else:
            gap = t - self._last_token[rid]
            self._itl.append(gap)
            self._itl_by_rid.setdefault(rid, []).append(gap)
        self._last_token[rid] = t
        self._n_tokens[rid] = self._n_tokens.get(rid, 0) + 1
        self._end = t

    def on_finish(self, rid: str, reason: str | None = None) -> None:
        """Terminal transition; ``reason`` (the finish_reason) feeds
        goodput — only normal finishes (stop/length, or legacy ``None``)
        can count as good requests."""
        self._finished += 1
        self._finish_reason[rid] = reason
        self._end = self.now()

    def on_preemption(self) -> None:
        self._preemptions += 1

    def on_admit(self, rid: str) -> None:
        """First admission of a request: records its queue wait
        (re-admissions after preemption are not new queue waits)."""
        if rid in self._admit_t or rid not in self._arrival:
            return
        t = self.now()
        self._admit_t[rid] = t
        self._queue_wait.append(t - self._arrival[rid])

    def on_prefill_complete(self, rid: str) -> None:
        """Disaggregated serving: the prefill phase finished (the
        prefill-role replica published the request's KV for handoff).
        First call wins — a retried handoff keeps the original mark."""
        if rid not in self._prefill_done_t:
            self._prefill_done_t[rid] = self.now()

    def on_handoff_landed(self, rid: str) -> None:
        """Disaggregated serving: the pulled KV was injected and the
        request re-admitted on a decode-role replica. First call wins,
        so re-pulls after a decode-replica death keep the original
        transfer latency."""
        if rid not in self._handoff_admit_t:
            self._handoff_admit_t[rid] = self.now()

    def ttft_breakdown(self) -> dict:
        """Split each request's TTFT into the three phases the disagg
        A/B attributes cost to: queue-wait (arrival -> first
        admission), prefill-compute (admission -> prefill finished),
        and handoff-transfer (prefill finished -> first token, i.e. the
        KV offer/pull/re-admission plus the decode replica's first
        step). Colocated requests have no prefill-done mark, so their
        compute span runs to the first token and handoff is 0 —
        schema-stable across both serving modes."""
        qw: list[float] = []
        pf: list[float] = []
        ho: list[float] = []
        for rid, t1 in self._first_token.items():
            t0 = self._arrival.get(rid)
            ta = self._admit_t.get(rid)
            if t0 is None or ta is None:
                continue
            qw.append(ta - t0)
            td = self._prefill_done_t.get(rid)
            if td is not None:
                pf.append(max(td - ta, 0.0))
                ho.append(max(t1 - td, 0.0))
            else:
                pf.append(max(t1 - ta, 0.0))
                ho.append(0.0)
        return {
            "ttft_queue_wait_p50_s": percentile(qw, 50),
            "ttft_queue_wait_p99_s": percentile(qw, 99),
            "ttft_prefill_p50_s": percentile(pf, 50),
            "ttft_prefill_p99_s": percentile(pf, 99),
            "ttft_handoff_p50_s": percentile(ho, 50),
            "ttft_handoff_p99_s": percentile(ho, 99),
        }

    def on_reject(self, kind: str) -> None:
        """An add_request rejection: kind is 'queue_full' or 'too_large'."""
        self.counters[f"rejected_{kind}"] += 1

    def on_outcome(self, finish_reason: str) -> None:
        """Count an abnormal terminal outcome by its finish_reason."""
        key = {"timeout": "timed_out", "nonfinite": "quarantined",
               "preempted_limit": "preempted_limit", "preempted": "drained",
               "injected": "injected", "shed": "shed"}.get(finish_reason)
        if key is not None:
            self.counters[key] += 1

    # ---- overload control (SERVING.md "Overload control & tenant
    # fairness") ----

    def set_fair(self, enabled: bool) -> None:
        """Arm the fair_enabled gauge (int, for Prometheus export)."""
        self.fair_enabled = int(bool(enabled))

    def set_brownout(self, enabled: bool) -> None:
        """Arm the brownout_enabled gauge (int, for Prometheus)."""
        self.brownout_enabled = int(bool(enabled))

    def on_brownout_level(self, level: int) -> None:
        """One engine step spent at ``level`` (0 = normal service) —
        feeds the current-level gauge and the per-level occupancy
        counters the bench reports as brownout-level occupancy."""
        self._brownout_level = int(level)
        if level in self._brownout_steps:
            self._brownout_steps[level] += 1

    def on_brownout_transition(self, old: int, new: int) -> None:
        self._brownout_transitions += 1

    def on_shed(self, tenant: int = 0, priority: int = 0) -> None:
        """One shed decision (admission quota/infeasibility or a
        brownout level-3 queue shed), attributed to its tenant and
        priority class — the shed-by-priority breakdown the fairness
        bench reports."""
        self._shed_by_priority[int(priority)] = (
            self._shed_by_priority.get(int(priority), 0) + 1)
        self._shed_by_tenant[int(tenant)] = (
            self._shed_by_tenant.get(int(tenant), 0) + 1)

    def tenant_of(self, rid: str) -> int:
        return self._tenant.get(rid, 0)

    def priority_of(self, rid: str) -> int:
        return self._priority.get(rid, 0)

    def per_tenant(self) -> dict[int, dict]:
        """Per-tenant latency/outcome view: {tenant: {"arrived",
        "finished", "ttft_p50_s", "ttft_p99_s", "shed"}} — finished
        counts normal finishes only (stop/length/legacy None), sheds
        count both admission sheds and terminal shed outcomes. This is
        what the fairness bench ranks arms by (cold-tenant p99 TTFT)."""
        tenants = (set(self._tenant.values())
                   | set(self._shed_by_tenant))
        out: dict[int, dict] = {}
        for t in sorted(tenants):
            rids = [r for r, tt in self._tenant.items() if tt == t]
            ttft = [self._first_token[r] - self._arrival[r]
                    for r in rids
                    if r in self._first_token and r in self._arrival]
            finished = sum(
                1 for r in rids
                if self._finish_reason.get(r, "")
                in (None, "stop", "length"))
            out[t] = {
                "arrived": len(rids),
                "finished": finished,
                "ttft_p50_s": percentile(ttft, 50),
                "ttft_p99_s": percentile(ttft, 99),
                "shed": self._shed_by_tenant.get(t, 0),
            }
        return out

    def shed_by_priority(self) -> dict[int, int]:
        return dict(self._shed_by_priority)

    def on_prefill(self, cached_tokens: int, total_tokens: int,
                   restored_tokens: int = 0) -> None:
        """One admission's prefill accounting: ``cached_tokens`` of the
        ``total_tokens`` context were served from the prefix cache (the
        engine only ran the suffix), ``restored_tokens`` of THOSE came
        back from the host spill tier. Feeds ``cache_hit_rate`` and the
        tier hit-rate breakdown."""
        self._prefill_tokens += total_tokens
        self._prefill_cached_tokens += cached_tokens
        self._prefill_restored_tokens += restored_tokens

    def on_prefix_counters(self, counters: dict) -> None:
        """Mirror the pool's prefix-cache page counters (lookups, hits,
        partial hits, evictions, COW copies) into the summary."""
        self._prefix_counters = dict(counters)

    # ---- KV tiering (SERVING.md "KV tiering & traffic harness") ----

    def set_host_tier(self, enabled: bool) -> None:
        """Arm the host_tier_enabled gauge (int, for Prometheus)."""
        self.host_tier_enabled = int(bool(enabled))

    def on_tier_stats(self, stats: dict) -> None:
        """Mirror the host tier's byte/page gauges (HostTier.stats())
        into the summary — called by the engine once per step."""
        self._tier_stats = dict(stats)

    def tier_hit_rates(self) -> dict:
        """Where prefill context tokens were served from: ``hbm``
        (prefix-cache pages already resident), ``host`` (restored from
        the spill tier), ``miss`` (recomputed). The three sum to 1 once
        any prefill ran; restored tokens are cached tokens, so
        hbm + host == cache_hit_rate."""
        t = self._prefill_tokens
        if t == 0:
            return {"hbm": 0.0, "host": 0.0, "miss": 0.0}
        host = self._prefill_restored_tokens / t
        hbm = (self._prefill_cached_tokens
               - self._prefill_restored_tokens) / t
        return {"hbm": hbm, "host": host, "miss": 1.0 - hbm - host}

    # ---- SLO goodput (ROADMAP item 5) ----

    def set_slo(self, ttft_p99_s: float | None = None,
                itl_p99_s: float | None = None) -> None:
        """Arm the SLO thresholds ``summary()`` scores goodput against.
        ``None`` leaves a dimension unconstrained."""
        self.slo_ttft_s = ttft_p99_s
        self.slo_itl_s = itl_p99_s

    def goodput_at_slo(self, ttft_p99_s: float | None = None,
                       itl_p99_s: float | None = None) -> float:
        """Requests/s that finished normally AND met the SLOs.

        A request is *good* when (a) its finish reason is a normal stop
        (``stop``/``length``; legacy callers that never passed a reason
        count too), (b) it emitted a first token, (c) TTFT <= the TTFT
        SLO, and (d) the p99 of its own inter-token gaps <= the ITL SLO
        (requests with < 2 tokens have no gaps and trivially pass).
        ``None`` SLOs are unconstrained. Denominator is the same wall
        time ``tokens_per_s`` uses; 0.0 before any time has passed.
        """
        wall = ((self._end - self._start)
                if self._start is not None and self._end is not None
                else 0.0)
        if wall <= 0:
            return 0.0
        good = 0
        for rid, reason in self._finish_reason.items():
            if reason not in (None, "stop", "length"):
                continue
            if rid not in self._first_token or rid not in self._arrival:
                continue
            ttft = self._first_token[rid] - self._arrival[rid]
            if ttft_p99_s is not None and ttft > ttft_p99_s:
                continue
            if itl_p99_s is not None:
                gaps = self._itl_by_rid.get(rid, [])
                if gaps and percentile(gaps, 99) > itl_p99_s:
                    continue
            good += 1
        return good / wall

    # ---- int8 KV quantization (SERVING.md "Quantized KV & weights") ----

    def set_kv_quant(self, enabled: bool) -> None:
        """Arm the kv_quant_enabled gauge (int, so Prometheus export —
        which skips non-numeric values — renders it)."""
        self.kv_quant_enabled = int(bool(enabled))

    def on_kv_quant_scale(self, scale_max: float) -> None:
        """Fold one prefill's max absmax scale into the running max."""
        self.kv_quant_scale_max = max(self.kv_quant_scale_max,
                                      float(scale_max))

    # ---- speculative decoding (SERVING.md "Speculative decoding") ----

    def set_spec(self, enabled: bool) -> None:
        """Arm the spec_enabled gauge (int, for Prometheus export)."""
        self.spec_enabled = int(bool(enabled))

    def on_spec_draft(self, proposed: int) -> None:
        """One drafter call for one slot: ``proposed`` tokens offered
        (0 = the drafter had nothing — the slot decodes normally)."""
        self._spec_draft_calls += 1
        if proposed > 0:
            self._spec_draft_hits += 1

    def on_spec_verify(self, drafted: int, accepted: int) -> None:
        """One slot's verify outcome: ``accepted`` of ``drafted`` draft
        tokens matched the engine's own samples (the step emitted
        accepted + 1 tokens before any eos/length truncation)."""
        self._spec_draft_tokens += drafted
        self._spec_accepted_tokens += accepted
        h = self._spec_hist.setdefault(drafted, [0, 0])
        h[0] += accepted
        h[1] += 1

    # ---- chunked prefill (SERVING.md "Chunked prefill & mixed steps") --

    def set_chunked(self, enabled: bool) -> None:
        """Arm the chunked_enabled gauge (int, for Prometheus export)."""
        self.chunked_enabled = int(bool(enabled))

    # ---- crash-consistent snapshots (serving/snapshot.py) ----

    def set_snapshots(self, enabled: bool) -> None:
        """Arm the snapshots_enabled gauge (int, for Prometheus)."""
        self.snapshots_enabled = int(bool(enabled))

    # ---- tensor parallelism (serving/parallel.py) ----

    def set_tp(self, tp: int, shard_kv_bytes_per_token: int = 0) -> None:
        """Arm the TP gauges: the engine's TP degree and the per-DEVICE
        KV bytes one cached token costs (== the full figure at tp=1)."""
        self.tp_degree = int(tp)
        self.tp_shard_kv_bytes_per_token = int(shard_kv_bytes_per_token)

    def set_pp(self, pp: int, waves: int = 1,
               bubble_frac: float = 0.0) -> None:
        """Arm the pipeline-parallel gauges: the pp degree, the mixed
        step's microbatch wave count, and the pipeline schedule's
        idle-stage (bubble) fraction ``(pp-1)/(waves+pp-1)``."""
        self.pp_degree = int(pp)
        self.pp_waves = int(waves)
        self.pipeline_bubble_frac = float(bubble_frac)

    def on_snapshot_stats(self, stats: dict) -> None:
        """Mirror the snapshot store's capture gauges
        (SnapshotStore.stats()) into the summary — called by the
        engine after each periodic capture."""
        self._snapshot_stats = dict(stats)

    # ---- multi-tenant LoRA (SERVING.md "Multi-tenant LoRA serving") --

    def set_lora(self, enabled: bool) -> None:
        """Arm the lora_enabled gauge (int, for Prometheus export)."""
        self.lora_enabled = int(bool(enabled))

    def on_lora_stats(self, stats: dict) -> None:
        """Mirror the adapter pool's gauges (AdapterPool.stats()) into
        the summary — called by the engine once per step. Keys land
        under a ``lora_`` prefix so render_prometheus emits them as the
        ``paddle_serving_lora_*`` family."""
        self._lora_stats = dict(stats)

    def on_mixed_step(self, prefill_tokens: int, decode_slots: int,
                      chunk_slots: int, in_flight: int) -> None:
        """One mixed-step dispatch: ``prefill_tokens`` prompt-chunk
        tokens across ``chunk_slots`` slots shared the program with
        ``decode_slots`` decoding/verifying slots; ``in_flight`` is the
        number of partially-prefilled requests resident after planning
        (slots mid-prompt, whether or not they got a chunk this step)."""
        self._mixed_steps += 1
        self._chunk_tokens += prefill_tokens
        self._chunks_dispatched += chunk_slots
        self._chunk_prefill_tokens_last = prefill_tokens
        self._chunk_decode_slots_last = decode_slots
        self._chunks_in_flight_last = in_flight

    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens accepted by the verify step."""
        if self._spec_draft_tokens == 0:
            return 0.0
        return self._spec_accepted_tokens / self._spec_draft_tokens

    def spec_draft_hit_rate(self) -> float:
        """Fraction of drafter calls that proposed at least one token."""
        if self._spec_draft_calls == 0:
            return 0.0
        return self._spec_draft_hits / self._spec_draft_calls

    def spec_accept_histogram(self) -> dict[int, dict]:
        """Accept stats keyed by draft length: {n_draft: {"steps",
        "accepted_mean", "accept_rate"}} — the per-length report
        (tests/test_serving_spec.py::TestSpecObservability)."""
        out = {}
        for n, (acc, steps) in sorted(self._spec_hist.items()):
            out[n] = {"steps": steps,
                      "accepted_mean": acc / steps if steps else 0.0,
                      "accept_rate": acc / (n * steps)
                      if n and steps else 0.0}
        return out

    def cache_hit_rate(self) -> float:
        """Fraction of prefill context tokens served from cached pages."""
        if self._prefill_tokens == 0:
            return 0.0
        return self._prefill_cached_tokens / self._prefill_tokens

    # ---- per-step gauges ----

    def on_step(self, queue_depth: int, pool_utilization: float) -> None:
        self._queue_depth.append(queue_depth)
        self._pool_util.append(pool_utilization)

    # ---- aggregation ----

    def ttfts(self) -> list[float]:
        return [self._first_token[r] - self._arrival[r]
                for r in self._first_token if r in self._arrival]

    def tpots(self) -> list[float]:
        out = []
        for r, n in self._n_tokens.items():
            if n > 1:
                out.append((self._last_token[r] - self._first_token[r])
                           / (n - 1))
        return out

    @property
    def total_tokens(self) -> int:
        return sum(self._n_tokens.values())

    def summary(self) -> dict:
        from .lora import AdapterPool as _AdapterPool
        from .snapshot import SnapshotStore as _SnapshotStore
        from .tiering import HostTier as _HostTier
        ttft = self.ttfts()
        tpot = self.tpots()
        tier_rates = self.tier_hit_rates()
        wall = ((self._end - self._start)
                if self._start is not None and self._end is not None else 0.0)
        return {
            "requests_finished": self._finished,
            "tokens_generated": self.total_tokens,
            "wall_s": wall,
            "tokens_per_s": (self.total_tokens / wall) if wall > 0 else 0.0,
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p99_s": percentile(ttft, 99),
            "tpot_mean_s": (sum(tpot) / len(tpot)) if tpot else 0.0,
            "itl_p50_s": percentile(self._itl, 50),
            "itl_p99_s": percentile(self._itl, 99),
            "preemptions": self._preemptions,
            "queue_depth_max": max(self._queue_depth, default=0),
            "queue_depth_mean": (sum(self._queue_depth)
                                 / len(self._queue_depth)
                                 if self._queue_depth else 0.0),
            "kv_util_mean": (sum(self._pool_util) / len(self._pool_util)
                             if self._pool_util else 0.0),
            "kv_util_peak": max(self._pool_util, default=0.0),
            "queue_wait_p50_s": percentile(self._queue_wait, 50),
            "queue_wait_p99_s": percentile(self._queue_wait, 99),
            # TTFT attribution (SERVING.md "Disaggregated serving"):
            # queue-wait / prefill-compute / handoff-transfer — always
            # present; handoff percentiles are 0 for colocated serving
            **self.ttft_breakdown(),
            "rejected": (self.counters["rejected_queue_full"]
                         + self.counters["rejected_too_large"]),
            "cache_hit_rate": self.cache_hit_rate(),
            "prefill_tokens": self._prefill_tokens,
            "prefill_cached_tokens": self._prefill_cached_tokens,
            "goodput_at_slo": self.goodput_at_slo(self.slo_ttft_s,
                                                  self.slo_itl_s),
            # always present (schema-stable for Prometheus scrapers);
            # err_bound = scale_max/2 is the worst-case |dequant - true|
            # of any element in the int8 cache
            "kv_quant_enabled": self.kv_quant_enabled,
            "kv_quant_scale_max": self.kv_quant_scale_max,
            "kv_quant_err_bound": self.kv_quant_scale_max / 2.0,
            # speculative decoding gauges/counters (schema-stable: zeros
            # with speculation off)
            "spec_enabled": self.spec_enabled,
            "spec_draft_tokens_total": self._spec_draft_tokens,
            "spec_accepted_tokens_total": self._spec_accepted_tokens,
            "spec_accept_rate": self.spec_accept_rate(),
            "spec_draft_hit_rate": self.spec_draft_hit_rate(),
            # chunked prefill / mixed-step composition (schema-stable:
            # zeros with chunking off)
            "chunked_enabled": self.chunked_enabled,
            "mixed_steps": self._mixed_steps,
            "chunk_tokens_total": self._chunk_tokens,
            "chunks_dispatched_total": self._chunks_dispatched,
            "chunk_prefill_tokens_last": self._chunk_prefill_tokens_last,
            "chunk_decode_slots_last": self._chunk_decode_slots_last,
            "chunks_in_flight": self._chunks_in_flight_last,
            # KV tiering (schema-stable: zeros with the tier off).
            # tier_hit_rate == cache_hit_rate (restored tokens ARE
            # cached tokens); the hbm/host/miss split is the breakdown.
            "host_tier_enabled": self.host_tier_enabled,
            "prefill_restored_tokens": self._prefill_restored_tokens,
            "tier_hit_rate": self.cache_hit_rate(),
            "tier_hbm_hit_rate": tier_rates["hbm"],
            "tier_host_hit_rate": tier_rates["host"],
            "tier_miss_rate": tier_rates["miss"],
            **{**_HostTier.zero_stats(), **self._tier_stats},
            # crash-consistent snapshots (schema-stable: zeros with
            # snapshotting off; the store's keys are snapshot_-prefixed)
            "snapshots_enabled": self.snapshots_enabled,
            **{**_SnapshotStore.zero_stats(), **self._snapshot_stats},
            # multi-tenant LoRA serving (schema-stable: zeros with LoRA
            # off). AdapterPool.stats() keys land under a lora_ prefix
            # — the paddle_serving_lora_* Prometheus family — so pool
            # gauges like "capacity" can never shadow a summary key.
            "lora_enabled": self.lora_enabled,
            **{(k if k.startswith("lora_") else "lora_" + k): v
               for k, v in {**_AdapterPool.zero_stats(),
                            **self._lora_stats}.items()},
            # tensor parallelism (schema-stable: tp_degree 1 on a
            # single-device engine) — the paddle_serving_tp_* family
            "tp_degree": self.tp_degree,
            "tp_shard_kv_bytes_per_token": self.tp_shard_kv_bytes_per_token,
            # pipeline parallelism (schema-stable: pp_degree 1, bubble
            # 0.0 on an unstaged engine) — the paddle_serving_pp_* family
            "pp_degree": self.pp_degree,
            "pp_waves": self.pp_waves,
            "pipeline_bubble_frac": self.pipeline_bubble_frac,
            # SLO-aware overload control (schema-stable zeros when fair
            # scheduling / the brownout ladder are off); the per-tenant
            # and per-priority flattenings below are dynamic keys, like
            # the pool counters — present once a tenant/priority is seen
            "fair_enabled": self.fair_enabled,
            "brownout_enabled": self.brownout_enabled,
            "brownout_level": self._brownout_level,
            "brownout_transitions": self._brownout_transitions,
            "brownout_level1_steps": self._brownout_steps.get(1, 0),
            "brownout_level2_steps": self._brownout_steps.get(2, 0),
            "brownout_level3_steps": self._brownout_steps.get(3, 0),
            **{f"tenant{t}_{k}": v
               for t, d in self.per_tenant().items()
               for k, v in d.items()},
            **{f"shed_priority{p}": n
               for p, n in sorted(self._shed_by_priority.items())},
            # pool counters live under prefix_* so they can never
            # shadow a summary key (the pool already uses that prefix
            # for most of them — normalise the stragglers)
            **{(k if k.startswith("prefix_") else "prefix_" + k): v
               for k, v in self._prefix_counters.items()},
            **self.counters,
        }
