"""Iteration-level (continuous-batching) scheduler — Orca, OSDI '22.

FCFS with a per-step prefill token budget: every engine step the
scheduler first guarantees the running slots their next decode-write
page (preempting from the youngest when the pool is exhausted —
preempt-and-recompute, vLLM's recompute policy), then admits waiting
requests in strict arrival order while slots, pool pages and the token
budget allow. Requests therefore join and leave the running batch at
token granularity; nothing ever waits for a whole batch to drain.

With ``fair=True`` (SERVING.md "Overload control & tenant fairness"),
global strict-FCFS admission becomes a weighted token-deficit queue
ACROSS tenants — the virtual-token-counter fairness of Sheng et al.
("Fairness in Serving Large Language Models", OSDI '24): each tenant
carries a virtual counter of service tokens consumed (scaled by its
weight); admission always serves the backlogged tenant with the
smallest counter, and a tenant going idle never banks credit (its
counter is lifted to the backlogged minimum when it returns). FCFS
*within* a tenant is preserved, so every individual stream stays
bitwise identical to ``generate()`` — only inter-request ordering
changes, which the per-request ``fold_in(PRNGKey(seed), token_index)``
sampling contract is already immune to. Per-tenant admission quotas
(``tenant_max_live`` running slots, ``tenant_max_queued_tokens``
queued work) bound how much of the engine one tenant can hold; the
queued-token gate is enforced by the ENGINE at ``add_request`` (it
owns the retry_after_s estimate), the live-slot gate here at head
selection (a tenant at its cap is skipped, not errored — its turn
comes back when a slot frees).

All state here is host-side Python (deques and integer lists); the
device-side consequences (block tables, active masks, position offsets)
are materialized by the engine as plain array inputs to its single
compiled decode program. Under tensor parallelism (serving/parallel.py)
nothing here changes: scheduler state is REPLICATED host metadata — one
block table, one refcount ledger, one admission queue feed every shard
of the TP group, because each shard holds its slice of every page.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from ..observability.trace import NULL_TRACER
from .errors import QueueFullError, RequestTooLargeError
from .kv_cache import KVCachePool, PoolExhaustedError

__all__ = ["Request", "SamplingParams", "Scheduler",
           "WAITING", "RUNNING", "FINISHED", "PREEMPTED"]

WAITING = "waiting"
RUNNING = "running"
PREEMPTED = "preempted"
FINISHED = "finished"


@dataclass
class SamplingParams:
    """Per-request decode controls (each becomes a per-slot array lane in
    the compiled decode step — changing them never retraces)."""
    temperature: float = 1.0
    top_p: float = 1.0
    do_sample: bool = False  # False -> greedy argmax
    seed: int = 0


@dataclass
class Request:
    rid: str
    prompt: list[int]
    max_new_tokens: int
    sampling: SamplingParams = field(default_factory=SamplingParams)
    eos_token_id: int | None = None

    # multi-tenant SLO classes (SERVING.md "Overload control & tenant
    # fairness"): tenant keys the fair-queue deficit counter and the
    # admission quotas; priority only ever decides WHICH queued request
    # a level-3 brownout sheds first (higher = more important) — neither
    # touches the compiled step or the emitted stream
    tenant: int = 0
    priority: int = 0

    # lifecycle
    state: str = WAITING
    arrival_seq: int = 0          # admission priority (FCFS tiebreak)
    tokens: list[int] = field(default_factory=list)   # generated so far
    finish_reason: str | None = None
    preemptions: int = 0

    # robustness (SERVING.md "Serving failure modes"): deadlines are
    # measured from arrival on the engine's injectable metrics clock and
    # enforced at step boundaries
    deadline_s: float | None = None        # arrival -> completion budget
    max_queue_wait_s: float | None = None  # arrival -> first admission
    arrival_t: float = 0.0                 # stamped by engine.add_request

    # cache bookkeeping (valid while RUNNING)
    slot: int | None = None
    pages: list[int] = field(default_factory=list)
    context_len: int = 0          # tokens currently materialized in cache
    # chunked prefill (SERVING.md "Chunked prefill & mixed steps"): the
    # materialization target of the CURRENT admission. A chunked admit
    # leaves context_len at the cached length and the engine streams the
    # suffix through the mixed step in budget-sized chunks, advancing
    # context_len until it reaches prefill_target; an unchunked admit
    # sets context_len = prefill_target in one shot (legacy behavior).
    prefill_target: int = 0
    # prefix-cache bookkeeping for the CURRENT admission: how many
    # leading tokens were served from cached pages (the engine prefills
    # only the suffix beyond them), and whether the last cached page was
    # a copy-on-write partial hit
    cached_len: int = 0
    cached_partial: bool = False
    # host-tier accounting for the CURRENT admission: how many of
    # cached_len tokens were restored from the host spill tier (they
    # skip recompute FLOPs but paid restore bytes — the scheduler
    # charged ceil(restored_len * restore_budget_frac) prefill-budget
    # tokens for them; SERVING.md "KV tiering & traffic harness")
    restored_len: int = 0
    # speculative decoding (serving/speculative.py): tokens the drafter
    # proposed for the NEXT step; the mixed program scores them at
    # positions context_len..context_len+len-1 and the engine clears the
    # list every step. Drafts never affect the emitted stream — only how
    # many tokens a step emits — so this is working state, not history.
    draft_tokens: list[int] = field(default_factory=list)
    # disaggregated serving (SERVING.md "Disaggregated serving"): a
    # prefill-only request stops at final-chunk completion — the engine
    # exports its KV to the handoff outbox instead of emitting the
    # first token, and finishes it with reason "handoff". The decode
    # side re-admits the handed-off request through the NORMAL path: a
    # fresh request whose full-prompt KV was injected matches
    # n_valid - 1 cached tokens (the cap above), so its admission
    # charges zero prefill-budget tokens beyond the one forced suffix
    # row that produces the first logits — bitwise identical to the
    # colocated final chunk.
    handoff: bool = False
    # multi-tenant LoRA (SERVING.md "Multi-tenant LoRA serving"): the
    # content digest (hex) of the adapter this request decodes with, ""
    # for the base model. adapter_slot is the AdapterPool slot pinned
    # for it while RUNNING (0 = the identity slot); acquired at admit,
    # released with the KV pages, so a preemption drops the pin but a
    # warm re-admit usually hits the pool's LRU cache.
    adapter: str = ""
    adapter_slot: int = 0

    @property
    def adapter_ns(self) -> bytes:
        """Prefix-cache namespace: adapters produce different KV for the
        same tokens, so cache identity is (adapter, tokens) — the digest
        bytes salt the pool's hash root (kv_cache._namespaced_root)."""
        return bytes.fromhex(self.adapter) if self.adapter else b""

    @property
    def recompute_len(self) -> int:
        """Prefill length on (re-)admission: the prompt plus all generated
        tokens except the last, which is the decode input (after a
        preemption the cache is rebuilt exactly to where it was)."""
        return len(self.prompt) + max(0, len(self.tokens) - 1)

    @property
    def prefilling(self) -> bool:
        """True while a RUNNING request still owes prefill chunks: its
        cache holds fewer tokens than this admission's target. A
        prefilling slot neither decodes nor drafts — it rides the mixed
        step's prefill lanes until context_len reaches the target."""
        return self.state == RUNNING and self.context_len < self.prefill_target

    @property
    def done(self) -> bool:
        return self.state == FINISHED


class Scheduler:
    def __init__(self, max_slots: int, prefill_token_budget: int = 2048,
                 max_queue_depth: int | None = None,
                 max_preemptions: int | None = None,
                 fair: bool = False,
                 tenant_weights: dict | None = None,
                 tenant_max_live: int | None = None,
                 tenant_max_queued_tokens: int | None = None):
        self.max_slots = max_slots
        self.prefill_token_budget = prefill_token_budget
        self.max_queue_depth = max_queue_depth
        self.max_preemptions = max_preemptions
        # tenant-aware fair scheduling + quotas (SERVING.md "Overload
        # control & tenant fairness"): fair=False keeps the strict
        # global FCFS this scheduler always had (the A/B baseline arm).
        # tenant_weights scales each tenant's virtual-token charge
        # (weight 2.0 = entitled to twice the service; default 1.0);
        # tenant_max_live caps RUNNING slots per tenant (enforced at
        # head selection); tenant_max_queued_tokens caps queued
        # prompt+decode tokens per tenant (enforced by the engine at
        # add_request, where the retry_after_s estimate lives).
        self.fair = bool(fair)
        self.tenant_weights = dict(tenant_weights or {})
        self.tenant_max_live = tenant_max_live
        self.tenant_max_queued_tokens = tenant_max_queued_tokens
        # the virtual token counters (Sheng et al., OSDI '24): service
        # tokens charged per tenant at admission, divided by the
        # tenant's weight — min-counter tenant is served next
        self._vtc: dict[int, float] = {}
        self.waiting: list[Request] = []   # kept sorted by arrival_seq
        self.running: dict[int, Request] = {}   # slot -> request
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self._arrival_counter = 0
        self.num_preemptions = 0
        # speculative decoding: the engine sets spec_k to its verify
        # step's row count (1 = plain decode). A verify step scores up
        # to spec_k tokens per running slot through the same weight
        # stream a prefill would use, so admission charges those extra
        # verify tokens against the SAME per-step prefill token budget —
        # one budget bounds the step's total token work.
        self.spec_k = 1
        # chunked prefill: when True (set by the engine), ``admit`` maps
        # pages and pins the cached prefix but leaves context_len at the
        # cached length — the engine streams the uncached suffix through
        # its mixed step in budget-metered chunks. The suffix then
        # charges the budget chunk by chunk AT DISPATCH, not at
        # admission, so admission only pays the host-tier restore toll.
        self.chunked = False
        # pipeline-parallel serving: the engine sets this to its mixed
        # step's microbatch wave count (pp when waving, else 1). The
        # engine's chunk planner wave-aligns non-final prefill bites to
        # multiples of the wave width chunk/pp_waves so a bite fills
        # whole waves instead of leaving the last wave half-empty — a
        # pacing hint only; chunk boundaries never change emitted
        # streams (the chunked-prefill parity contract).
        self.pp_waves = 1
        # multi-tenant LoRA: the engine points this at its AdapterPool
        # when lora serving is on. ``admit`` pins the head's adapter
        # slot alongside its KV pages; a request whose adapter payload
        # is lost/corrupt lands in ``admit_failures`` for the engine to
        # finish with a typed reason (never silently served base
        # weights), while pool-full exhaustion makes the head WAIT —
        # retryable, like any other resource.
        self.adapters = None
        self.admit_failures: list[Request] = []
        # injected by the engine when tracing is on. The scheduler owns
        # every queue/slot state transition, so it owns the request-track
        # lifecycle spans: "queued" opens at add/_requeue and closes at
        # admission (or terminal eviction from the queue); "running"
        # brackets slot occupancy exactly (_release closes it before any
        # requeue, keeping the track's begin/end stack balanced).
        self.tracer = NULL_TRACER

    # ---- queue ----

    def add(self, req: Request, pool: KVCachePool | None = None) -> None:
        """Enqueue a new request. With ``pool`` given, rejects requests
        that could NEVER run (prompt+decode pages beyond the pool's
        capacity) with :class:`RequestTooLargeError` — without this,
        ``admit()`` would spin on the queue head forever. A full bounded
        queue (``max_queue_depth``) rejects with
        :class:`QueueFullError` (backpressure, not an engine fault)."""
        if (self.max_queue_depth is not None
                and len(self.waiting) >= self.max_queue_depth):
            raise QueueFullError(
                f"waiting queue at max_queue_depth={self.max_queue_depth}; "
                f"request {req.rid!r} rejected (shed load or retry "
                f"elsewhere)")
        if pool is not None:
            need = pool.pages_for(len(req.prompt) + req.max_new_tokens)
            if need > pool.capacity:
                # prefix-cache accounting: only the UNCACHED suffix has
                # to be newly allocated — a prompt whose cached prefix
                # pages already sit in the pool can run even when its
                # total page count exceeds the capacity check above
                cached = 0
                if pool.cache_enabled:
                    cached = len(pool.match_prefix(
                        req.prompt, namespace=req.adapter_ns).full_pages)
                if need - cached > pool.capacity:
                    raise RequestTooLargeError(
                        f"request {req.rid!r} needs {need} pages for its "
                        f"prompt ({len(req.prompt)} tokens) + "
                        f"{req.max_new_tokens} decode tokens "
                        f"({cached} cached), but the pool has only "
                        f"{pool.capacity} allocatable pages — it "
                        f"could never run")
        if self.fair:
            # VTC lift (Sheng et al.): a tenant returning from idle is
            # lifted to the minimum counter of the currently-active
            # tenants, so idling never BANKS credit to burst with later
            # — fairness is over backlogged work, not history
            active = ({r.tenant for r in self.waiting}
                      | {r.tenant for r in self.running.values()})
            if req.tenant not in active and active:
                floor = min(self._vtc.get(t, 0.0) for t in active)
                self._vtc[req.tenant] = max(
                    self._vtc.get(req.tenant, 0.0), floor)
        req.arrival_seq = self._arrival_counter
        self._arrival_counter += 1
        req.state = WAITING
        self.waiting.append(req)
        self.tracer.begin("queued", track=req.rid,
                          prompt=len(req.prompt),
                          max_new=req.max_new_tokens)

    def _requeue(self, req: Request) -> None:
        """Put a preempted request back, keeping FCFS (arrival) order."""
        req.state = PREEMPTED
        keys = [r.arrival_seq for r in self.waiting]
        self.waiting.insert(bisect.bisect_left(keys, req.arrival_seq), req)
        self.tracer.begin("queued", track=req.rid,
                          preemptions=req.preemptions)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def live_requests(self) -> list[Request]:
        """Every non-terminal request (waiting + running), arrival order.
        This is what a fleet router fails over when it ejects the engine:
        each entry's rid/prompt/sampling is enough to replay it bitwise
        on another replica (SERVING.md "Engine fleet & failover")."""
        live = list(self.waiting) + list(self.running.values())
        return sorted(live, key=lambda r: r.arrival_seq)

    # ---- preemption ----

    def _preempt_youngest(self, pool: KVCachePool) -> Request:
        victim = max(self.running.values(), key=lambda r: r.arrival_seq)
        self._release(victim, pool)
        victim.preemptions += 1
        self.num_preemptions += 1
        self.tracer.instant("preempt", track=victim.rid,
                            preemptions=victim.preemptions)
        self.tracer.bump("preemptions")
        if (self.max_preemptions is not None
                and victim.preemptions > self.max_preemptions):
            # starvation guard: a request bounced out of the pool more
            # than max_preemptions times stops competing — it finishes
            # with a classified reason instead of thrashing recompute
            # prefills forever (the engine emits the terminal event)
            victim.state = FINISHED
            victim.finish_reason = "preempted_limit"
        else:
            self._requeue(victim)
        return victim

    def _release(self, req: Request, pool: KVCachePool,
                 register: bool = True) -> None:
        """Drop the request's slot and page REFERENCES (shared prefix
        pages may outlive it under other holders). With ``register``
        (every release except poison quarantine), its materialized
        prefix — full pages plus the frozen partial tail — is indexed
        first, so a preempted request's recompute, or a later request
        sharing the prompt, can map these pages instead of re-prefilling.

        A request released MID-PREFILL (context_len < prefill_target —
        a chunked prefill preempted between chunks) registers NOTHING:
        its later pages hold partially-written or zero content, and even
        the completed leading chunks are an unfinished admission —
        registration commits only on the final chunk (engine) or at a
        post-prefill release here. The page references are still
        dropped, so a mid-chunk preemption can never leak COW refs."""
        self.tracer.end("running", track=req.rid,
                        context_len=req.context_len)
        if register and req.pages and not req.prefilling:
            seq = (req.prompt + req.tokens)[:req.context_len]
            pool.register_prefix(seq, req.pages, include_partial=True,
                                 namespace=req.adapter_ns)
        pool.release(req.pages)
        if req.adapter_slot and self.adapters is not None:
            self.adapters.release(req.adapter_slot)
            req.adapter_slot = 0
        req.pages = []
        req.cached_len = 0
        req.cached_partial = False
        req.restored_len = 0
        req.draft_tokens = []   # drafts are per-step state; recompute
                                # re-proposes from the same history
        if pool.state:
            pool.state_release(req.slot)
        self._free_slots.append(req.slot)
        del self.running[req.slot]
        req.slot = None
        req.context_len = 0

    def finish(self, req: Request, pool: KVCachePool, reason: str) -> None:
        """Terminal transition from ANY live state: a running request
        releases its slot and pages; a waiting/preempted one just leaves
        the queue (deadline expiry and drain finish requests that never
        held resources). Poisoned/injected finishes never register their
        pages in the prefix index (the engine quarantined them already —
        registering NaN content would serve it to future hits)."""
        register = reason not in ("nonfinite", "injected")
        if req.slot is not None:
            self._release(req, pool, register=register)
        else:
            if req in self.waiting:
                self.waiting.remove(req)
                self.tracer.end("queued", track=req.rid)
            if req.pages:
                pool.release(req.pages)
                req.pages = []
        req.state = FINISHED
        req.finish_reason = reason

    # ---- the per-step scheduling decision ----

    def verify_token_reserve(self) -> int:
        """Verify tokens the next step may score beyond the plain
        one-per-slot decode: (spec_k - 1) draft rows per running slot.
        The engine subtracts this from the prefill budget it threads
        through ``admit`` so speculation and prefill bursts share one
        per-step token-work bound (0 when speculation is off). Slots
        still mid-prefill don't verify (they neither decode nor draft),
        so they don't reserve."""
        return (self.spec_k - 1) * sum(1 for r in self.running.values()
                                       if not r.prefilling)

    def ensure_decode_pages(self, pool: KVCachePool) -> list[Request]:
        """Before a decode step: every running request writes its next
        token at position context_len — make sure that page exists.
        Oldest requests are served first; when the pool is exhausted the
        youngest running request is preempted (possibly the one asking).
        Returns the requests preempted this call."""
        preempted: list[Request] = []
        for req in sorted(self.running.values(), key=lambda r: r.arrival_seq):
            if req.slot is None:  # lost its slot to an earlier preemption
                continue
            # a speculative step writes the decode token AND the drafts
            # optimistically, so the page guarantee covers all of them;
            # rejected drafts just leave (zeroed) headroom the request
            # would have grown into anyway
            needed = (pool.pages_for(req.context_len + 1
                                     + len(req.draft_tokens))
                      - len(req.pages))
            while needed > 0:
                try:
                    req.pages.extend(pool.alloc(needed))
                    needed = 0
                except PoolExhaustedError:
                    victim = self._preempt_youngest(pool)
                    preempted.append(victim)
                    if victim is req:
                        break  # it preempted itself; nothing left to grow
        return preempted

    # ---- tenant accounting (SERVING.md "Overload control & tenant
    # fairness") ----

    def live_slots(self, tenant: int) -> int:
        """RUNNING slots this tenant holds right now (the quantity
        ``tenant_max_live`` caps)."""
        return sum(1 for r in self.running.values() if r.tenant == tenant)

    def queued_tokens(self, tenant: int) -> int:
        """Queued service tokens (prompt + decode budget) this tenant
        holds in the waiting queue — what ``tenant_max_queued_tokens``
        caps at ``add_request`` (the engine raises the typed shed)."""
        return sum(max(r.recompute_len, 1) + r.max_new_tokens
                   for r in self.waiting if r.tenant == tenant)

    def _tenant_weight(self, tenant: int) -> float:
        w = float(self.tenant_weights.get(tenant, 1.0))
        return w if w > 0 else 1.0

    def _select_head(self) -> Request | None:
        """The next admission candidate. FCFS mode: the oldest waiting
        request (skipping tenants at their live-slot cap when quotas
        are on). Fair mode: the oldest waiting request OF the
        backlogged tenant with the smallest weighted virtual token
        counter — FCFS within the tenant, min-deficit across tenants;
        ties break by arrival for determinism. Returns None when every
        waiting request belongs to a tenant at its live cap."""
        cap = self.tenant_max_live
        if not self.fair:
            if cap is None:
                return self.waiting[0] if self.waiting else None
            for req in self.waiting:
                if self.live_slots(req.tenant) < cap:
                    return req
            return None
        best: Request | None = None
        best_key: tuple | None = None
        seen: set[int] = set()
        for req in self.waiting:   # arrival-sorted -> per-tenant FCFS head
            t = req.tenant
            if t in seen:
                continue
            seen.add(t)
            if cap is not None and self.live_slots(t) >= cap:
                continue
            key = (self._vtc.get(t, 0.0), req.arrival_seq)
            if best_key is None or key < best_key:
                best, best_key = req, key
        return best

    def admit(self, pool: KVCachePool, limit: int | None = None,
              budget: int | None = None,
              first: bool = True) -> list[Request]:
        """Admit waiting requests while a slot, the pool, and the
        per-step prefill token budget allow — in strict FCFS order by
        default, or fair-queue order across tenants with ``fair=True``
        (``_select_head``; FCFS within a tenant either way). Stops at
        the first selected head that does not fit (no queue jumping —
        the same head is re-selected next step, so it can never be
        starved by smaller requests behind it). Returns the admitted
        requests with slot + prompt pages assigned; the engine runs
        their prefills.

        The engine calls this with ``limit=1`` in a loop; ``budget``
        carries the remaining step budget across those calls and
        ``first=False`` says an admission already happened this step
        (the first admission of a step ignores the budget so an
        oversized prompt cannot deadlock). With ``chunked`` set the
        uncached suffix does NOT gate or charge admission — the engine
        meters it chunk by chunk at dispatch — so only the host-tier
        restore toll counts here, and the admitted request starts with
        ``context_len`` at its cached length and ``prefill_target`` at
        the full materialization goal."""
        admitted: list[Request] = []
        budget = self.prefill_token_budget if budget is None else budget
        while (self.waiting and self._free_slots
               and (limit is None or len(admitted) < limit)):
            req = self._select_head()
            if req is None:
                break  # every waiting tenant is at its live-slot cap
            n_valid = max(req.recompute_len, 1)
            # prefix-cache lookup: a fresh request caps the match at
            # n_valid - 1 (at least one suffix token must run through the
            # prefill program to produce its first logits); a recompute
            # (req.tokens non-empty — the prefill's prediction is
            # discarded anyway) may match fully and skip the program
            match = None
            cached = 0
            if pool.cache_enabled:
                cap = n_valid if req.tokens else n_valid - 1
                seq = req.prompt + req.tokens[:-1]
                match = pool.match_prefix(seq, max_tokens=cap,
                                          namespace=req.adapter_ns)
                # the optimistic (pre-restore) view: the whole cache
                # hierarchy hit, including host-tier tokens that still
                # have to be restored at commit time
                cached = match.total_cached
            suffix = n_valid - cached
            # only the UNCACHED suffix charges the prefill token budget
            # — plus the restore toll on host-tier tokens: they skip
            # recompute FLOPs but pay restore bytes, charged like a
            # partial cache hit at restore_budget_frac per token.
            # Chunked mode defers the suffix charge to chunk dispatch,
            # so only the restore toll gates admission here.
            charge = (0 if self.chunked else suffix) + pool.restore_charge(match)
            if (admitted or not first) and charge > budget:
                break
            n_new = (pool.pages_for(n_valid)
                     - (len(match.full_pages) if match else 0))
            if n_new > pool.num_available:
                break
            # multi-tenant LoRA: pin the head's adapter slot BEFORE any
            # pool mutation (the acquire may stream weights from the
            # host tier / evict an idle slot, but it never touches KV
            # pages, so a later rollback only has to release the pin).
            # Pool-full exhaustion makes the head WAIT like page
            # exhaustion; a lost/corrupt payload is terminal — the
            # request moves to admit_failures for the engine to finish
            # with a typed reason, and the NEXT head gets its turn.
            aslot = 0
            if req.adapter and self.adapters is not None:
                from .lora import (AdapterExhaustedError,
                                   AdapterUnavailableError)
                try:
                    aslot = self.adapters.acquire(req.adapter_ns)
                except AdapterExhaustedError:
                    break
                except AdapterUnavailableError:
                    self.waiting.remove(req)
                    self.tracer.end("queued", track=req.rid)
                    self.admit_failures.append(req)
                    continue
            # commit order matters: pin the matched pages FIRST so this
            # admission's own allocs (including restores) cannot
            # LRU-evict them, then restore the host-tier chain, then
            # allocate the suffix pages, then materialize the COW /
            # host-partial copy. Rollback on failure leaves the pool as
            # found — up to restored pages, which stay behind as
            # refcount-0 CACHED pages (warm for the retry).
            pinned: list[int] = []
            if match is not None and match.hit:
                pinned = list(match.full_pages)
                if match.partial_page is not None:
                    pinned.append(match.partial_page)
                pool.acquire(pinned)
            chain_pages: list[int] = []
            restored_tok = 0
            if match is not None and match.chain:
                chain_pages, restored_tok = pool.restore_chain(match)
            chain_ok = (match is None
                        or len(chain_pages) == len(match.chain))
            # the partial tail applies only after a fully-restored
            # chain (it continues the LAST chain page's content)
            use_hbm_partial = bool(chain_ok and match is not None
                                   and match.partial_page is not None)
            host_partial = None
            if (chain_ok and match is not None
                    and match.host_partial_key is not None):
                host_partial = pool.fetch_host_partial(match)
            # re-derive the ACTUAL cached length from what committed
            # (a failed restore shortens it; the difference recomputes)
            partial_q = 0
            if use_hbm_partial:
                partial_q = match.partial_len
            elif host_partial is not None:
                partial_q = match.host_partial_len
            if match is not None:
                cached = ((len(match.full_pages) + len(chain_pages))
                          * pool.page_size + partial_q)
                suffix = n_valid - cached
            n_new = (pool.pages_for(n_valid)
                     - (len(match.full_pages) if match else 0)
                     - len(chain_pages))
            try:
                pages = pool.alloc(n_new)
            except PoolExhaustedError:
                pool.release(pinned)
                pool.release(chain_pages)
                if aslot and self.adapters is not None:
                    self.adapters.release(aslot)
                self.tracer.instant("admit_rollback", track=req.rid,
                                    need=n_new,
                                    available=pool.num_available)
                self.tracer.bump("admit_rollbacks")
                break  # injected exhaustion (serving.alloc) — the head
                       # stays queued, never torn out of the FCFS order
            if match is not None and match.partial_page is not None:
                if use_hbm_partial:
                    # copy-at-map COW: the hitter gets a fresh page
                    # holding a copy of the cached partial page and
                    # extends THAT; the cached page itself is never
                    # written, then unpinned
                    pool.cow_into(match.partial_page, pages[0])
                pool.release([match.partial_page])
            elif host_partial is not None:
                # same COW rule, copy sourced from the host tier —
                # restored straight into the hitter's first suffix page
                pool.restore_partial_into(pages[0], host_partial)
                restored_tok += match.host_partial_len
            if match is not None:
                pool.count_match(match)
            self.waiting.remove(req)
            if self.fair:
                # charge the tenant's virtual token counter with the
                # service this admission buys (context to materialize +
                # decode budget), scaled by the tenant's weight — the
                # deficit that decides who is served next. Recomputes
                # after preemption charge again: they are real service.
                self._vtc[req.tenant] = (
                    self._vtc.get(req.tenant, 0.0)
                    + (n_valid + req.max_new_tokens)
                    / self._tenant_weight(req.tenant))
            req.pages = ((list(match.full_pages) if match else [])
                         + chain_pages + pages)
            req.cached_len = cached
            req.restored_len = restored_tok
            req.cached_partial = partial_q > 0
            req.adapter_slot = aslot
            req.slot = self._free_slots.pop()
            if pool.state:      # a model with per-slot recurrent state
                pool.state_admit(req.slot, req.rid)
            req.state = RUNNING
            req.prefill_target = n_valid
            # chunked: start at the cached length; the engine's mixed
            # step advances context_len chunk by chunk up to the target
            req.context_len = cached if self.chunked else n_valid
            self.running[req.slot] = req
            if self.tracer.enabled:
                self.tracer.end("queued", track=req.rid)
                self.tracer.instant("admit", track=req.rid, slot=req.slot,
                                    cached=cached, suffix=suffix,
                                    restored=restored_tok)
                self.tracer.begin("running", track=req.rid)
            admitted.append(req)
            if self.chunked:
                # the suffix charges at chunk dispatch; a prefilling slot
                # doesn't verify, so no (spec_k - 1) reserve either —
                # admission pays only the restore toll
                budget -= pool.restore_charge_tokens(restored_tok)
            else:
                # an admitted slot also joins this step's verify fan-out
                # (spec_k - 1 draft rows), charged like prefill tokens —
                # and restored tokens charge their restore toll
                budget -= (suffix + pool.restore_charge_tokens(restored_tok)
                           + (self.spec_k - 1))
        return admitted
