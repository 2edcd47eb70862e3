"""Continuous-batching serving engine over the paged KV-cache pool.

The engine owns exactly TWO compiled programs for its lifetime:

- the 1-token decode step, always over the fixed ``[max_slots]`` slot
  axis with block tables, position offsets, the active mask and every
  per-request sampling parameter as ARRAY inputs — requests joining,
  finishing or being preempted change array *values*, never shapes, so
  ``decode_program_count()`` stays at 1 across arbitrary churn
  (asserted by tests/test_serving.py);
- the MIXED step, fixed shape ``[max_slots, chunk]``: each slot carries
  ``(start_pos, n_new)`` as the ``seq_lens``/``n_live`` array lanes and
  processes either a budget-sized PREFILL CHUNK (``forced`` lane set:
  its rows are teacher-forced prompt tokens) or its decode input plus
  up to k-1 speculative draft tokens — Orca's iteration-level batching
  with Sarathi-Serve's chunked prefill. One program serves prefill,
  decode+verify, and any mixture; the old O(log max_len) pow2
  suffix-bucket prefill family is gone.

Long prompts stream through the mixed step in chunks metered by the
per-step prefill token budget, so decode slots never stall behind a
prompt: a chunking slot occupies its lane with prompt rows while every
other slot keeps decoding in the same dispatch. All rows share the one
grouped GQA core and the paged scatter-at-write path (fp and int8 KV);
within a chunk, row j sits at pool position ``start_pos + j`` and
attends causally up to itself. Speculative verify is the degenerate
mixed step whose new tokens are draft rows instead of prompt rows: row
j is ACCEPTED iff it equals the row j-1 sample (Leviathan), rejected
rows are zeroed in-program, and a ``forced`` slot accepts all its rows
by construction. Only the rows whose sample can be emitted are sampled:
``spec_k`` a slot, a chunk's last live row or a verify slot's first
``spec_k``. ``step_program_counts()`` reports both step shapes
and each stays pinned at 1 (O(1) programs, not O(prompt-length) or
O(accept-pattern)).

With ``prefix_cache=True`` (default) the pool indexes full pages by
chained content hash, shares them across requests via refcounts,
reuses partial pages copy-on-write, and LRU-evicts refcount-0 cached
pages when allocation would otherwise fail — see SERVING.md "Prefix
caching". Prefix registration commits on the FINAL chunk: a request
preempted mid-prompt registers nothing (and still drops its page
refs), so partial prompts can never serve future hits.

Determinism: greedy decode is argmax over logits that are bitwise equal
to ``LlamaForCausalLM.generate()``'s (shared attention core, masked
padding contributes exact zeros — see SERVING.md); sampled requests
draw token *n* with ``fold_in(PRNGKey(seed), n)`` so a preempted and
recomputed request reproduces its original stream regardless of slot
placement, chunk boundaries, or batch composition.

Robustness (SERVING.md "Serving failure modes"): every failure mode is
a classified per-request outcome or a typed :mod:`.errors` exception,
never an engine-wide hang — bounded-queue backpressure and
reject-at-add for impossible requests, per-request deadlines enforced
at step boundaries on the injectable metrics clock, a per-request
preemption cap, a non-finite logit sentinel that quarantines only the
offending slot (its pages are scrubbed back to zero so the pool's
masked-garbage-is-zero invariant survives reuse), zero-progress stall
detection (chunk progress counts as progress), and ``drain()`` for
graceful (SIGTERM) shutdown. The blocking per-step device sync runs
under ``watch("serving.step")`` and the fault sites ``serving.step`` /
``serving.prefill`` / ``serving.decode`` / ``serving.alloc`` make all
of it deterministically chaos-testable.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed import fault as _fault
from ..observability.trace import PROFILE_TRACER
from ..ops.pallas.paged_attention import latent_grid_steps
from .errors import (AdmissionShedError, EngineDrainingError,
                     LatentCacheError, QueueFullError, RecurrentStateError,
                     RequestTooLargeError, SchedulerStalledError)
from .kv_cache import HybridCache, KVCachePool, declared_cache_layers
from .metrics import ServingMetrics
from .scheduler import FINISHED, Request, SamplingParams, Scheduler
from .snapshot import (RequestSnapshot, load_engine_snapshot,
                       save_engine_snapshot)

__all__ = ["ServingEngine", "BrownoutConfig"]

# consecutive zero-progress steps tolerated before SchedulerStalledError:
# a deterministic livelock (preempt-self treadmill, un-admittable queue
# head) repeats identically every step, while a transient injected alloc
# storm recovers as soon as its fault spec stops matching — so > 1, small
_STALL_PATIENCE = 3

_log = logging.getLogger(__name__)


@dataclass
class BrownoutConfig:
    """The brownout ladder's watermarks (SERVING.md "Overload control &
    tenant fairness"; RESILIENCE.md "Overload playbook").

    Queue-depth/wait-time watermarks drive staged degradation, one
    level per ``dwell_steps`` window (hysteresis — the ladder never
    flaps on a single-step spike): the engine escalates one level when
    ``queue_depth >= high_queue`` or the oldest queued request has
    waited ``high_wait_s`` (metrics clock), and de-escalates one level
    when ``queue_depth <= low_queue`` (and, if set, every queued wait
    is back under ``low_wait_s``). The levels are pure HOST-SIDE
    policy — no compiled shape moves, ``step_program_counts()`` stays
    ``{"decode": 1, "mixed": 1}`` across every transition:

    - level 1: the per-step prefill token budget shrinks to
      ``budget_frac`` of its configured value (admission + chunk
      metering slow down; decode latency recovers first);
    - level 2: speculation is suspended — the drafter is host-side, so
      skipping it just leaves the draft lanes empty;
    - level 3: the lowest-priority queued requests are shed
      (``finish_reason="shed"``, retryable) until the queue is back at
      the high watermark.
    """

    high_queue: int = 8
    low_queue: int = 2
    high_wait_s: float | None = None
    low_wait_s: float | None = None
    budget_frac: float = 0.5
    dwell_steps: int = 2

    def __post_init__(self):
        if self.low_queue > self.high_queue:
            raise ValueError("brownout low_queue must be <= high_queue "
                             f"(got {self.low_queue} > {self.high_queue})")
        if not 0.0 < self.budget_frac <= 1.0:
            raise ValueError("brownout budget_frac must be in (0, 1], "
                             f"got {self.budget_frac}")
        if self.dwell_steps < 1:
            raise ValueError("brownout dwell_steps must be >= 1, "
                             f"got {self.dwell_steps}")


class ServingEngine:
    def __init__(self, model, num_pages: int, page_size: int,
                 max_slots: int = 4, max_pages_per_slot: int | None = None,
                 prefill_token_budget: int = 2048, kv_dtype=None,
                 clock=None, max_queue_depth: int | None = None,
                 max_preemptions: int | None = None,
                 step_timeout_s: float | None = None,
                 drain_timeout_s: float | None = 30.0,
                 watchdog=None, prefix_cache: bool = True,
                 tracer=None, flight_recorder=None,
                 kv_quant: bool = False, speculative=None,
                 host_tier=None, chunked: bool = True,
                 prefill_chunk: int = 64, snapshot_store=None,
                 snapshot_interval: int = 16, tp: int = 1,
                 tp_devices=None, pp: int = 1,
                 pp_microbatch: bool = True,
                 fair_scheduling: bool = False,
                 tenant_weights=None, tenant_max_live: int | None = None,
                 tenant_max_queued_tokens: int | None = None,
                 shed_infeasible: bool = False, brownout=None,
                 lora=None):
        cfg = model.config
        self.model = model
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_pages_per_slot = (max_pages_per_slot
                                   if max_pages_per_slot is not None
                                   else (num_pages - 1))
        # a model with per-slot recurrent state (its config declares it:
        # ``cache_layers()``; SERVING.md "Models with recurrent state").
        # Whatever assumes that a request's whole state is its pages is
        # switched off where it is a default (the prefix cache) and
        # refused where it is asked for.
        page_formats, state_layers = declared_cache_layers(cfg)
        self._recurrent = bool(state_layers)
        # a model with a latent cache (``("latent", width)``: one page
        # array a layer; SERVING.md "Models with a latent cache"): the
        # prefix cache stays on, and what reads or writes a page as a K
        # and a V array of heads is refused the same way
        self._latent = page_formats[0][0] == "latent"
        # either kind is handed a ``HybridCache`` by the step programs
        # and hands its own counters back with it
        self._hybrid = self._recurrent or self._latent
        if self._hybrid:
            int8_kv = kv_quant or (kv_dtype is not None
                                   and jnp.dtype(kv_dtype) == jnp.int8)
            for asked, what in (
                    (speculative, "speculative decoding"),
                    (host_tier, "the host tier"),
                    (snapshot_store, "a snapshot store"), (lora, "LoRA"),
                    (int8_kv, "an int8 KV pool")):
                if asked:
                    self._refuse(what)
        if self._recurrent and prefix_cache:
            prefix_cache = False
            _log.warning(
                "%s keeps per-slot recurrent state: the prefix cache "
                "is off (a cached page holds K/V, not the state at "
                "its boundary)", type(cfg).__name__)
        self.prefix_cache = prefix_cache
        self._step_counts = None    # the last step's HybridCache.counts
        # tensor parallelism (serving/parallel.py; SERVING.md
        # "Tensor-parallel serving"): tp=N spans this engine over N
        # devices (tp_devices, default the first N visible) — the KV
        # pool shards its kv-head dim, weights go column/row-parallel,
        # and each of the TWO step programs compiles as ONE shard_map
        # over the mp axis. tp=1 is exactly the single-device engine.
        # Un-shardable configs raise TPConfigError here, not a shape
        # crash inside the compiled step.
        # pipeline parallelism (same file; SERVING.md "Pipeline-parallel
        # serving"): pp=P stages the decoder over a leading pp mesh axis
        # — embed + the first L/pp layers on stage 0, lm_head + the last
        # on stage P-1 — with the KV pool stacked and carved per stage.
        # Each step is STILL one jit(shard_map) over the full pp×mp
        # mesh; stage handoff is a ppermute ring inside the program.
        # pp_microbatch splits the mixed step's chunk into pp waves so
        # stages overlap instead of idling (pp-1)/pp of the time.
        from .parallel import TPContext, validate_tp_config
        validate_tp_config(cfg, tp, pp)
        self.tp = int(tp)
        self.pp = int(pp)
        self._tp = (TPContext(model, tp, devices=tp_devices, pp=pp)
                    if tp > 1 or pp > 1 else None)
        self._pp_waves = self.pp if (self.pp > 1 and pp_microbatch) else 1
        # int8 KV mode: kv_quant=True, or kv_dtype="int8"/jnp.int8 — the
        # pool stores int8 codes + fp32 absmax scales, quantized at
        # scatter time and dequantized inside the one shared decode core
        # (quantization/serving.py; SERVING.md "Quantized KV & weights")
        if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
            kv_quant = True
        self.kv_quant = kv_quant
        # host-RAM spill tier (serving/tiering.py): True -> defaults, an
        # int -> byte budget, or a ready HostTier instance — share ONE
        # instance across homogeneous replicas and their spilled prefix
        # pages become fleet-wide warm cache (identical weights produce
        # bitwise-identical KV). Requires the prefix cache (spill keys
        # are its content hashes).
        self.pool = KVCachePool.from_config(
            cfg, num_pages, page_size,
            dtype=(jnp.bfloat16 if kv_quant or kv_dtype is None
                   else kv_dtype),
            cache_enabled=prefix_cache, quantized=kv_quant,
            host_tier=host_tier if prefix_cache else None,
            sharding=(self._tp.kv_shardings() if self._tp else None),
            tp_degree=self.tp, pp_degree=self.pp, max_slots=max_slots)
        # every (re-)admission must fit the slot's block table and the
        # rope table — admission_check guards the window up front
        self._ctx_pages = min(self.max_pages_per_slot,
                              self.pool.pages_for(
                                  cfg.max_position_embeddings))
        # SLO-aware overload control (SERVING.md "Overload control &
        # tenant fairness"): fair_scheduling turns on the weighted
        # virtual-token-counter queue across tenants (FCFS within a
        # tenant — streams stay bitwise identical to generate());
        # tenant_max_live / tenant_max_queued_tokens are per-tenant
        # admission quotas; shed_infeasible arms the deadline-
        # infeasibility gate; brownout takes a BrownoutConfig (or True
        # for defaults) to arm the staged-degradation ladder.
        self.scheduler = Scheduler(
            max_slots, prefill_token_budget,
            max_queue_depth=max_queue_depth,
            max_preemptions=max_preemptions,
            fair=fair_scheduling, tenant_weights=tenant_weights,
            tenant_max_live=tenant_max_live,
            tenant_max_queued_tokens=tenant_max_queued_tokens)
        # multi-tenant LoRA serving (serving/lora.py; SERVING.md
        # "Multi-tenant LoRA serving"): lora=True builds an AdapterPool
        # with defaults, a dict forwards kwargs, or pass a ready pool
        # (share one across colocated engines). Per-slot adapter
        # selection is an ARRAY lane of the two step programs — gather
        # by adapter-table index — so churn across thousands of
        # registered adapters never recompiles. tp>1 is gated here: the
        # adapter buffers are replicated host-built arrays and the TP
        # step's lane layout doesn't carry them yet.
        from .lora import AdapterPool
        if lora is True:
            lora = AdapterPool(cfg)
        elif isinstance(lora, dict):
            lora = AdapterPool(cfg, **lora)
        self.adapters: AdapterPool | None = lora or None
        if self.adapters is not None and (self.tp > 1 or self.pp > 1):
            from .errors import TPConfigError
            raise TPConfigError(
                "multi-tenant LoRA serving is single-shard for now: "
                "adapter buffers are not laid out for the TP/PP step "
                "programs (pass tp=1, pp=1 or lora=None)")
        self.scheduler.adapters = self.adapters
        if brownout is True:
            brownout = BrownoutConfig()
        elif brownout is False:
            brownout = None
        self._brownout: BrownoutConfig | None = brownout
        self._brownout_level = 0
        self._brownout_since = 0       # engine step of the last transition
        self._shed_infeasible = bool(shed_infeasible)
        # step-duration EMA on the metrics clock: the ONLY timing input
        # to the deterministic retry_after_s / infeasibility estimators
        self._step_dt_ema: float | None = None
        # speculative decoding (serving/speculative.py; SERVING.md
        # "Speculative decoding"): pass a SpeculativeConfig, an int k,
        # or True for defaults. Draft rows ride the mixed step's row
        # axis; the drafter runs host-side every step.
        from .speculative import SpeculativeConfig
        if speculative is True:
            speculative = SpeculativeConfig()
        elif speculative is False:
            speculative = None
        elif isinstance(speculative, int):
            speculative = SpeculativeConfig(k=int(speculative))
        self._spec: SpeculativeConfig | None = speculative
        self._drafter = speculative.make_drafter() if speculative else None
        self.scheduler.spec_k = speculative.k if speculative else 1
        # chunked prefill (SERVING.md "Chunked prefill & mixed steps"):
        # chunked=True streams admitted prompts through the mixed step
        # in prefill_chunk-sized bites interleaved with decode;
        # chunked=False runs the whole suffix through the same program
        # inside the admission loop (legacy whole-prompt pacing — the
        # A/B baseline arm). Either way the mixed step's row count is
        # ONE compile-time constant: max(prefill_chunk, spec_k).
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        self.chunked = bool(chunked)
        self.prefill_chunk = int(prefill_chunk)
        self._chunk = max(self.prefill_chunk, self.scheduler.spec_k)
        if self._pp_waves > 1:
            # the microbatched mixed step splits its row axis into
            # pp equal waves — round the compile-time chunk up so the
            # wave width K/waves is integral (a few extra padded rows,
            # never a second program shape)
            self._chunk = -(-self._chunk // self._pp_waves) * self._pp_waves
        self.scheduler.chunked = self.chunked
        self.scheduler.pp_waves = self._pp_waves
        # crash-consistent snapshots (serving/snapshot.py; RESILIENCE.md
        # "Serving recovery playbook"): with a SnapshotStore attached,
        # every snapshot_interval steps the engine captures each live
        # request's resumable state — tokens so far plus its KV pages,
        # exported host-side with ONE batched device_get — so a fleet
        # router can bound failover replay to the tokens since the last
        # capture, and save_snapshot/restore give warm process restart.
        if snapshot_interval < 1:
            raise ValueError(f"snapshot_interval must be >= 1, "
                             f"got {snapshot_interval}")
        self.snapshot_store = snapshot_store
        self.snapshot_interval = int(snapshot_interval)
        # set this (or pass drain(snapshot_path=...)) to make SIGTERM
        # drains persist in-flight state instead of finishing it
        self.drain_snapshot_path: str | None = None
        self.metrics = ServingMetrics(clock)
        self.metrics.set_kv_quant(kv_quant)
        self.metrics.set_spec(speculative is not None)
        self.metrics.set_host_tier(self.pool.host_tier is not None)
        self.metrics.set_chunked(self.chunked)
        self.metrics.set_snapshots(snapshot_store is not None)
        self.metrics.set_tp(self.tp,
                            self.pool.kv_bytes_per_token_shard())
        self.metrics.set_pp(self.pp, self._pp_waves,
                            self.pipeline_bubble_frac())
        self.metrics.set_fair(fair_scheduling)
        self.metrics.set_brownout(self._brownout is not None)
        self.metrics.set_lora(self.adapters is not None)
        # observability (OBSERVABILITY.md): the tracer is shared with
        # the scheduler (request-lifecycle spans) and the pool
        # (eviction/COW/quarantine events). Without one the engine holds
        # the process-wide PROFILE_TRACER, which records exactly while a
        # JAX profiler session is on; a tracer passed here is on from its
        # construction (build it on the metrics' clock so spans and
        # percentiles line up). The flight recorder subscribes to a
        # passed tracer's event stream and is auto-dumped at terminal
        # conditions (stall, nonfinite, drain, watchdog timeout).
        self.tracer = tracer if tracer is not None else PROFILE_TRACER
        self.scheduler.tracer = self.tracer
        self.pool.tracer = self.tracer
        self.flight_recorder = flight_recorder
        if flight_recorder is not None and tracer is not None:
            tracer.add_sink(flight_recorder.record)
        # retrace detection: last-seen compiled-program count PER STEP
        # SHAPE ("decode", "mixed") — every shape is a first-class
        # program with its own sentinel
        self._step_traces: dict[str, int] = {}
        self._wd_hooked: set[int] = set()
        self.step_timeout_s = step_timeout_s
        self.drain_timeout_s = drain_timeout_s
        self._watchdog = watchdog
        self._state = model.state_dict(include_non_persistable_buffer=True)
        if self._tp is not None:
            # one-time placement onto the mesh (column/row/vocab layout
            # from the creation-time weight specs); pp>1 first folds the
            # per-layer keys into [L, ...] stacks whose leading dim
            # shards on the pp axis
            if self.pp > 1:
                self._state = self._tp.stage_state(self._state)
            self._state = self._tp.shard_state(self._state)
        self._requests: dict[str, Request] = {}
        # disaggregated serving (SERVING.md "Disaggregated serving"):
        # finished-prefill KV exports waiting to be offered over the
        # fleet wire — filled by _handoff_finish at final-chunk
        # completion, drained by the EngineServer via take_handoffs()
        self._handoff_outbox: list[RequestSnapshot] = []
        self._rid_counter = itertools.count()
        self._steps = 0
        self._idle_steps = 0
        self._draining = False
        self._guard = None
        self.last_drain_events: list[dict] = []
        self._decode_step = self._build_decode_step()
        self._mixed_step = self._build_mixed_step()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def add_request(self, prompt, max_new_tokens: int,
                    sampling: SamplingParams | None = None,
                    eos_token_id: int | None = None,
                    rid: str | None = None,
                    deadline_s: float | None = None,
                    max_queue_wait_s: float | None = None,
                    tenant: int = 0, priority: int = 0,
                    prefill_only: bool = False,
                    adapter=None) -> str:
        """Admission control happens HERE, not in the scheduler loop:
        a request that can never run raises RequestTooLargeError, a full
        bounded queue raises QueueFullError, a draining engine raises
        EngineDrainingError, and an exhausted per-tenant quota or an
        infeasible deadline raises AdmissionShedError (with a computed
        ``retry_after_s``) — all typed (errors.py, each carrying a
        machine-readable ``retryable`` flag), all counted
        (metrics.counters). Callers holding a retryable rejection don't
        have to implement the retry themselves: a
        ``serving.fleet.FleetRouter`` front-end routes around full and
        draining replicas automatically (SERVING.md "Engine fleet &
        failover"). ``deadline_s`` / ``max_queue_wait_s`` are budgets
        from arrival on the metrics clock, enforced at step boundaries
        with ``finish_reason="timeout"``. ``tenant`` scopes the request
        under the fair scheduler and the admission quotas; ``priority``
        (larger = more important, default 0) orders brownout level-3
        shedding — neither changes the tokens a stream produces.
        ``prefill_only=True`` marks a disaggregated-serving handoff
        request (SERVING.md "Disaggregated serving"): the engine runs
        the prompt through its mixed-step chunks, then — instead of
        emitting the first token — exports the finished KV to the
        handoff outbox (:meth:`take_handoffs`) and finishes the request
        with reason ``"handoff"``; a decode-role replica emits every
        token of the stream. ``adapter`` names the LoRA adapter to
        decode with (a registered name, hex digest, digest bytes, or
        LoRAAdapter — resolved by the engine's AdapterPool; requires
        ``lora=...`` at construction): an unknown adapter is rejected
        HERE with AdapterUnavailableError, and the stream is bitwise
        identical to ``generate()`` with that adapter merged into the
        base weights."""
        with self.tracer.span("add_request", step=self._steps):
            if self._draining:
                raise EngineDrainingError(
                    "engine is draining (preempted or shut down); retry on "
                    "another replica (serving.fleet.FleetRouter skips "
                    "draining replicas at placement time)")
            prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
            if not prompt:
                raise ValueError("prompt must be non-empty")
            if prefill_only and self._hybrid:
                self._refuse("a prefill-only hand-off")
            adapter_hex = ""
            if adapter is not None and adapter != "":
                from .lora import AdapterUnavailableError
                if self.adapters is None:
                    raise AdapterUnavailableError(
                        "engine was built without lora=...; pass "
                        "lora=True (or an AdapterPool) to serve adapters")
                adapter_hex = self.adapters.resolve(adapter).hex()
            try:
                self.admission_check(len(prompt), max_new_tokens)
            except RequestTooLargeError:
                self.metrics.on_reject("too_large")
                raise
            rid = rid if rid is not None else f"req-{next(self._rid_counter)}"
            old = self._requests.get(rid)
            if old is not None:
                if not old.done:
                    raise ValueError(f"duplicate request id {rid!r}")
                # a FINISHED record is safe to supersede — the disagg
                # router legitimately re-admits a rid after its prefill
                # phase finished here with reason "handoff" (fallback
                # recompute landing back on the warm prefill replica)
                del self._requests[rid]
            # chaos site: an injected admission fault models a crash in the
            # overload-control path itself — typed, keyed by rid
            _fault.trip("serving.admission", step=self._steps, path=rid)
            self._check_overload_gates(len(prompt), max_new_tokens,
                                       int(tenant), int(priority), deadline_s)
            req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                          sampling=sampling or SamplingParams(),
                          eos_token_id=eos_token_id,
                          deadline_s=deadline_s,
                          max_queue_wait_s=max_queue_wait_s,
                          arrival_t=self.metrics.now(),
                          tenant=int(tenant), priority=int(priority),
                          handoff=bool(prefill_only), adapter=adapter_hex)
            try:
                self.scheduler.add(req, self.pool)
            except QueueFullError:
                self.metrics.on_reject("queue_full")
                raise
            except RequestTooLargeError:
                self.metrics.on_reject("too_large")
                raise
            self._requests[rid] = req
            self.metrics.on_arrival(rid, tenant=int(tenant),
                                    priority=int(priority))
            return rid

    def register_adapter(self, adapter) -> str:
        """Register a :class:`serving.lora.LoRAAdapter` with this
        engine's AdapterPool and return its content digest (hex) — the
        handle ``add_request(adapter=...)``, fleet ``submit`` and
        snapshots carry. Registration spills the payload to the pool's
        host tier; device residency is paid lazily at first admission."""
        from .lora import AdapterUnavailableError
        if self.adapters is None:
            raise AdapterUnavailableError(
                "engine was built without lora=...; pass lora=True "
                "(or an AdapterPool) to register adapters")
        return self.adapters.register(adapter)

    def admission_check(self, prompt_len: int, max_new_tokens: int) -> None:
        """Raise RequestTooLargeError if a request of this geometry can
        NEVER run here, regardless of current load. Pure — no counters,
        no state: ``add_request`` wraps it with the reject accounting,
        and ``serving.fleet.FleetRouter`` calls it at submit time so an
        impossible request is refused fleet-wide before it occupies
        queue space anywhere (homogeneous replicas all reject it
        identically, hence ``RequestTooLargeError.retryable = False``)."""
        total = prompt_len + max_new_tokens
        need = self.pool.pages_for(total)
        if need > self.max_pages_per_slot:
            raise RequestTooLargeError(
                f"request needs {need} pages "
                f"(max_pages_per_slot={self.max_pages_per_slot})")
        # any (re-)admission must fit the context window: the longest
        # possible recompute is prompt + max_new - 1 tokens
        ctx = self._ctx_pages * self.page_size
        if total - 1 > ctx:
            raise RequestTooLargeError(
                f"request context ({total} tokens) exceeds the context "
                f"window of {ctx} tokens ({self._ctx_pages} pages; "
                f"bounded by max_position_embeddings and "
                f"max_pages_per_slot)")

    def _check_overload_gates(self, prompt_len: int, max_new_tokens: int,
                              tenant: int, priority: int,
                              deadline_s: float | None) -> None:
        """Load-DEPENDENT admission gates, layered over the
        load-independent geometry check in :meth:`admission_check`:
        the per-tenant queued-token quota, then the opt-in
        deadline-infeasibility shed. Both raise
        :class:`AdmissionShedError` (retryable, with a deterministic
        ``retry_after_s`` drain estimate) BEFORE the request holds any
        queue slot or pool page — shedding at the door is what keeps a
        doomed request from evicting feasible work later."""
        need = prompt_len + max_new_tokens
        cap = self.scheduler.tenant_max_queued_tokens
        if cap is not None:
            held = self.scheduler.queued_tokens(tenant)
            if held + need > cap:
                retry = self._drain_eta_s(held)
                self.metrics.on_reject("quota")
                self.metrics.on_shed(tenant, priority)
                self.tracer.instant("admission_shed", kind="tenant_quota",
                                    tenant=tenant)
                raise AdmissionShedError(
                    f"tenant {tenant} queued-token quota exhausted "
                    f"({held} held + {need} requested > cap {cap}); "
                    f"retry after ~{retry:.3f}s",
                    retry_after_s=retry, kind="tenant_quota",
                    tenant=tenant)
        if self._shed_infeasible and deadline_s is not None:
            eta = self._completion_eta_s(prompt_len, max_new_tokens)
            if eta is not None and eta > deadline_s:
                retry = self._drain_eta_s(self._queued_service_tokens())
                self.metrics.on_reject("infeasible")
                self.metrics.on_shed(tenant, priority)
                self.tracer.instant("admission_shed",
                                    kind="deadline_infeasible",
                                    tenant=tenant)
                raise AdmissionShedError(
                    f"deadline {deadline_s:.3f}s is infeasible: estimated "
                    f"completion ~{eta:.3f}s behind the current backlog; "
                    f"retry after ~{retry:.3f}s",
                    retry_after_s=retry, kind="deadline_infeasible",
                    tenant=tenant)

    def _effective_prefill_budget(self) -> int:
        """The per-step prefill/chunk token budget AFTER brownout:
        level >= 1 shrinks it to ``budget_frac`` of the configured
        value — a host-side scalar, never a compiled shape."""
        base = self.scheduler.prefill_token_budget
        if self._brownout is not None and self._brownout_level >= 1:
            base = max(1, int(base * self._brownout.budget_frac))
        return base

    def _token_capacity_per_step(self) -> int:
        """Service tokens one step can retire: the (brownout-effective)
        prefill budget plus one decode token per slot."""
        return max(1, self._effective_prefill_budget() + self.max_slots)

    def _queued_service_tokens(self) -> int:
        """Total service tokens (recompute + decode budget) held by the
        waiting queue — the backlog the drain estimators divide down."""
        return sum(max(r.recompute_len, 1) + r.max_new_tokens
                   for r in self.scheduler.waiting)

    def _drain_eta_s(self, tokens: int) -> float:
        """Deterministic drain-rate estimate behind every
        ``retry_after_s`` hint: queued service tokens over per-step
        token capacity, scaled by the step-duration EMA on the metrics
        clock. 0.0 before the first timed step — an honest "no data
        yet", never a fabricated constant."""
        if self._step_dt_ema is None or self._step_dt_ema <= 0.0:
            return 0.0
        return (tokens / self._token_capacity_per_step()
                * self._step_dt_ema)

    def _completion_eta_s(self, prompt_len: int,
                          max_new_tokens: int) -> float | None:
        """Estimated queue wait + prefill + decode for a NEW arrival:
        the backlog drains first, then its own prefill streams at the
        effective chunk budget, then ~one decoded token per step. None
        before the first timed step (no EMA -> no estimate -> the
        infeasibility gate never sheds on a cold engine)."""
        if self._step_dt_ema is None or self._step_dt_ema <= 0.0:
            return None
        queue_steps = (self._queued_service_tokens()
                       / self._token_capacity_per_step())
        own_steps = (prompt_len / self._effective_prefill_budget()
                     + max_new_tokens)
        return (queue_steps + own_steps) * self._step_dt_ema

    def step(self) -> list[dict]:
        """One scheduling iteration: expire deadlines, admit newly
        runnable requests (chunked: map pages only; unchunked: run the
        whole prefill inline), guarantee decode pages (preempting if
        needed), then ONE batched dispatch over the running slots —
        prefill chunks and decode/verify rows share the mixed program;
        a pure-decode step keeps the cheap ``[max_slots]`` program.
        Returns this step's token/finish events. A zero-progress step
        with work still pending raises SchedulerStalledError instead of
        letting ``run_to_completion`` busy-loop."""
        if not self.scheduler.has_work():
            return []
        # one parent span per call, its children covering the whole of
        # the step (OBSERVABILITY.md "Engine step phases")
        with self.tracer.span("step", step=self._steps) as span:
            return self._step(span)

    def _step(self, span) -> list[dict]:
        # key this step's serving.alloc fault draws by the ENGINE step
        # (not the process-global training cursor) so probabilistic
        # storms vary over the engine's lifetime deterministically
        self.pool.fault_step = self._steps
        _fault.trip("serving.step", step=self._steps)
        tr = self.tracer
        t_step0 = self.metrics.now()
        events: list[dict] = []
        with tr.span("deadline_sweep", queue=self.scheduler.queue_depth):
            self._expire_deadlines(events)
            if self._draining:
                self._flush_waiting(events)
        if not self._draining and self._brownout is not None:
            # one hysteresis tick of the brownout ladder BEFORE the
            # budget is computed, so a fresh transition takes effect
            # this very step (level-3 queue sheds land in `events`)
            with tr.span("brownout", level=self._brownout_level):
                self._update_brownout(events)
        with tr.span("admission"):
            budget = self._admit(events)
        # drafts are proposed BEFORE the page guarantee so
        # ensure_decode_pages covers the speculative writes too
        if self._spec is not None and self.scheduler.running:
            if self._brownout_level >= 2:
                # brownout level 2: suspend speculation — the drafter is
                # pure host code, so "off" is just empty draft lanes;
                # the mixed program's row count never moves
                for req in self.scheduler.running.values():
                    req.draft_tokens = []
            else:
                self._propose_drafts()
        with tr.span("ensure_pages"):
            preempted = self.scheduler.ensure_decode_pages(self.pool)
            for victim in preempted:
                self.metrics.on_preemption()
                if victim.state == FINISHED:  # hit the max_preemptions cap
                    self.metrics.on_outcome("preempted_limit")
                    self.metrics.on_finish(victim.rid, "preempted_limit")
                    self._trace_finish(victim, "preempted_limit")
                    events.append({"rid": victim.rid, "token": None,
                                   "finished": True,
                                   "finish_reason": "preempted_limit"})
        chunk_tokens, program = 0, "none"
        slots = len(self.scheduler.running)
        if slots:
            chunk_tokens, program = self._run_batch(events, max(budget, 0))
        if span is not None:
            span.args.update(program=program, slots=slots,
                             chunk_tokens=chunk_tokens)
        with tr.span("bookkeeping"):
            self._note_retraces()
            self.metrics.on_prefix_counters(self.pool.counters)
            if self.pool.host_tier is not None:
                self.metrics.on_tier_stats(self.pool.host_tier.stats())
            if self.adapters is not None:
                self.metrics.on_lora_stats(self.adapters.stats())
            self.metrics.on_step(self.scheduler.queue_depth,
                                 self.pool.utilization())
            self._steps += 1
            self._check_progress(events, chunk_tokens)
        if (self.snapshot_store is not None
                and self._steps % self.snapshot_interval == 0):
            # capture at the step boundary: pages hold exactly
            # context_len tokens, positions beyond are zeros (rejected
            # rows were zeroed in-program) or unreached stale content —
            # the tail page is sanitized host-side at export
            with tr.span("snapshot_capture"):
                self._capture_snapshots()
        # feed the step-duration EMA (metrics clock) the retry_after_s /
        # infeasibility estimators divide by; a zero-dt step (virtual
        # clock not advanced) contributes nothing
        dt = self.metrics.now() - t_step0
        if dt > 0.0:
            self._step_dt_ema = (dt if self._step_dt_ema is None
                                 else 0.8 * self._step_dt_ema + 0.2 * dt)
        return events

    def _admit(self, events: list[dict]) -> int:
        """The step's admissions; returns the prefill/chunk token budget
        that is left for the dispatch."""
        tr = self.tracer
        # the verify/chunk rows and any admission prefill share ONE
        # per-step token-work bound: the (brownout-effective) prefill
        # budget, minus the (spec_k - 1) verify rows each decoding slot
        # may score
        budget = (self._effective_prefill_budget()
                  - self.scheduler.verify_token_reserve())
        if not self._draining:
            # admit one request at a time. Unchunked: run its prefill
            # immediately so the NEXT admission's prefix lookup sees the
            # pages this prefill just registered (a same-step burst
            # sharing a system prompt prefills the common prefix once).
            # Chunked: just map pages — the suffix streams through the
            # mixed step below, and registration commits on the final
            # chunk.
            first = True
            while True:
                batch = self.scheduler.admit(self.pool, limit=1,
                                             budget=budget, first=first)
                if not batch:
                    break
                req = batch[0]
                first = False
                self.metrics.on_admit(req.rid)
                self.metrics.on_prefill(req.cached_len, req.prefill_target,
                                        req.restored_len)
                if self.chunked:
                    budget -= self.pool.restore_charge_tokens(
                        req.restored_len)
                    if not req.prefilling:
                        # recompute fully served from the prefix cache:
                        # the pages already hold the context bit-for-bit
                        # — no chunks owed, the stored last token drives
                        # the next decode row
                        tr.instant("prefill_cached", track=req.rid,
                                   cached=req.cached_len)
                else:
                    budget -= (req.context_len - req.cached_len
                               + self.pool.restore_charge_tokens(
                                   req.restored_len)
                               + (self.scheduler.spec_k - 1))
                    with tr.span("prefill_dispatch", rid=req.rid):
                        self._run_prefill(req, events)
        # adapter admit failures (lost/corrupt payload at acquire —
        # serving.lora_fetch chaos or a dropped host tier): terminal,
        # typed, never silently served base weights
        for req in self.scheduler.admit_failures:
            self._finish_abnormal(req, "adapter_unavailable", events)
        self.scheduler.admit_failures.clear()
        return budget

    def _check_progress(self, events: list[dict], chunk_tokens: int) -> None:
        """The stall backstop at the end of a step."""
        if events or chunk_tokens or not self.scheduler.waiting:
            # chunk tokens are progress even before any emission: a
            # long prompt legitimately spends several steps mid-prefill
            self._idle_steps = 0
            return
        # work is pending but nothing was admitted, decoded or finished
        # (the preempt-self livelock / un-admittable-head shape). A
        # deterministic livelock repeats this identically every step —
        # after _STALL_PATIENCE of them, surface the evidence instead of
        # letting run_to_completion busy-loop.
        self._idle_steps += 1
        if self._idle_steps < _STALL_PATIENCE:
            return
        head = self.scheduler.waiting[0]
        snapshot = {
            "step": self._steps,
            "idle_steps": self._idle_steps,
            "queue_depth": self.scheduler.queue_depth,
            "head_rid": head.rid,
            "head_needs_pages": self.pool.pages_for(
                max(head.recompute_len, 1)),
            "free_pages": self.pool.num_free,
            "capacity": self.pool.capacity,
            "running": len(self.scheduler.running),
        }
        self.tracer.instant("stall", idle_steps=self._idle_steps,
                            queue=self.scheduler.queue_depth)
        dump = self._dump_flight("scheduler_stalled", snapshot)
        if dump is not None:
            snapshot["flight_recorder"] = dump
        raise SchedulerStalledError(
            f"{snapshot['idle_steps']} zero-progress steps with "
            f"{snapshot['queue_depth']} request(s) pending: head "
            f"{head.rid!r} needs {snapshot['head_needs_pages']} "
            f"pages, {snapshot['free_pages']} free "
            f"(capacity {snapshot['capacity']})", snapshot)

    def stream(self):
        """Drive the engine to completion, yielding events as they are
        produced: ``{"rid", "token", "finished", "finish_reason"}``
        (abnormal finishes — timeout/nonfinite/preempted_limit/drain —
        carry ``token=None``). If a preemption guard is attached and
        trips (SIGTERM), the engine drains and the drain's terminal
        events are yielded before returning."""
        while self.scheduler.has_work():
            if self._preemption_pending():
                self.drain(timeout_s=self.drain_timeout_s)
                yield from self.last_drain_events
                return
            yield from self.step()

    def run_to_completion(self, max_steps: int | None = None) -> dict:
        """Drain the queue; returns {rid: generated token list}. On a
        tripped preemption guard the engine drains gracefully and every
        unfinished request ends with ``finish_reason="preempted"``."""
        steps = 0
        while self.scheduler.has_work():
            if self._preemption_pending():
                self.drain(timeout_s=self.drain_timeout_s)
                break
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(f"engine did not drain in {steps} steps")
        return {rid: list(r.tokens) for rid, r in self._requests.items()}

    def drain(self, timeout_s: float | None = None,
              snapshot_path: str | None = None) -> dict:
        """Graceful shutdown: stop admission, evict the waiting queue as
        ``finish_reason="preempted"`` ("retry elsewhere" — nothing was
        computed for them), let the running slots decode to their own
        finish until ``timeout_s`` (metrics clock) runs out, then evict
        the stragglers as preempted too. Returns the per-request outcome
        report {rid: {finish_reason, tokens, retriable}}; the terminal
        events produced during the drain are kept in
        ``last_drain_events``. Idempotent; after a drain,
        ``add_request`` raises EngineDrainingError.

        With ``snapshot_path`` (or ``drain_snapshot_path`` set), the
        drain takes the FAST path instead of decoding stragglers to
        completion: persist every in-flight request's resumable state
        with :meth:`save_snapshot`, then evict them all as retriable
        ``preempted`` outcomes. A warm restart
        (``ServingEngine.restore(path)``) continues every stream
        bitwise — the SIGTERM alternative when finishing all requests
        would blow the termination grace period."""
        events: list[dict] = []
        if snapshot_path is None:
            snapshot_path = self.drain_snapshot_path
        if snapshot_path is not None and not self._draining:
            self.save_snapshot(snapshot_path)
            self._draining = True
            self._flush_waiting(events)
            for req in list(self.scheduler.running.values()):
                self._finish_abnormal(req, "preempted", events)
            self.last_drain_events = events
            report = {rid: {"finish_reason": r.finish_reason,
                            "tokens": list(r.tokens),
                            "retriable": r.finish_reason == "preempted"}
                      for rid, r in self._requests.items()}
            self._dump_flight("drain", {
                "snapshot_path": snapshot_path,
                "outcomes": {rid: o["finish_reason"]
                             for rid, o in report.items()}})
            return report
        self._draining = True
        t0 = self.metrics.now()
        self._flush_waiting(events)
        while self.scheduler.running:
            if (timeout_s is not None
                    and self.metrics.now() - t0 >= timeout_s):
                for req in list(self.scheduler.running.values()):
                    self._finish_abnormal(req, "preempted", events)
                break
            events.extend(self.step())
        # the last step may have preempted a straggler back to waiting
        # AFTER that step's own flush — classify it before reporting
        self._flush_waiting(events)
        self.last_drain_events = events
        report = {rid: {"finish_reason": r.finish_reason,
                        "tokens": list(r.tokens),
                        "retriable": r.finish_reason == "preempted"}
                  for rid, r in self._requests.items()}
        self._dump_flight("drain", {
            "outcomes": {rid: o["finish_reason"]
                         for rid, o in report.items()}})
        return report

    # ------------------------------------------------------------------
    # crash-consistent snapshots (serving/snapshot.py)
    # ------------------------------------------------------------------

    def _refuse(self, what: str) -> None:
        """A feature asked of a model whose cache is not K/V pages
        alone: the named error of its kind."""
        name = type(self.model.config).__name__
        if self._recurrent:
            raise RecurrentStateError(
                f"{name} keeps per-slot recurrent state: the engine cannot "
                f"honour {what}, which takes a request's pages for its "
                f"whole state (SERVING.md \"Models with recurrent state\")")
        raise LatentCacheError(
            f"{name} keeps a latent cache, one row a token a layer: the "
            f"engine cannot honour {what}, which has not been carried "
            f"over from K/V pages of heads (SERVING.md \"Models with a "
            f"latent cache\")")

    def save_snapshot(self, path: str) -> str:
        """Durable warm-restart snapshot: capture every live request's
        resumable state NOW and persist it through the checkpoint
        commit protocol (stage into ``<path>.tmp``, ``COMMIT`` marker,
        rename — RESILIENCE.md). A crash mid-save leaves a torn staging
        dir that :meth:`restore` rejects; the previous committed
        snapshot at ``path`` is replaced only by the atomic rename."""
        if self._hybrid:
            self._refuse("save_snapshot")
        snaps = self._capture_requests()
        # "tp"/"pp" are informational: payloads are full logical pages
        # (the capture device_get gathers shards, and the stacked pp
        # pool emits the same per-layer payload order), so a tp=2 or
        # pp=2 snapshot restores into a tp=1 engine and vice versa
        save_engine_snapshot(path, snaps, meta={
            "steps": self._steps, "kv_quant": self.kv_quant,
            "page_size": self.page_size, "tp": self.tp, "pp": self.pp})
        self.metrics.counters["snapshot_saves"] += 1
        self.tracer.instant("snapshot_save", requests=len(snaps),
                            step=self._steps)
        return path

    def restore(self, path: str) -> list[str]:
        """Warm restart: load a committed on-disk snapshot into this
        (fresh) engine and re-admit every request in its original
        arrival order, seeded with the tokens it had already generated
        — the streams continue bitwise from where the dead process
        stopped (determinism: seed + token index reproduce every
        sample; the injected KV only saves recompute). Raises
        :class:`CheckpointCorruptionError` on a torn or unverifiable
        snapshot dir. Returns the restored rids."""
        if self._hybrid:
            self._refuse("restore")
        snaps, _meta = load_engine_snapshot(path)
        return [self.restore_request(s) for s in snaps]

    def restore_request(self, snap: RequestSnapshot,
                        tenant: int = 0, priority: int = 0) -> str:
        """Re-admit one snapshotted request (fleet failover and warm
        restart both land here). The snapshot's KV payloads — if any,
        and if their digests still verify — are injected into the pool
        as refcount-0 cached pages, so the ordinary admission prefix
        match maps them and the request resumes with zero (or near-
        zero) recompute; any verification failure just downgrades to
        the full-recompute path, which is bitwise-identical anyway.
        ``tenant``/``priority`` are re-attached by the caller (the
        snapshot format is unchanged; the fleet router carries them on
        its records) and the SURVIVOR's per-tenant quotas apply: a
        failed-over request that would bust the survivor's quota is
        refused with AdmissionShedError and stays queued at the router
        for the next placement attempt."""
        if self._hybrid:
            self._refuse("restore_request")
        if self._draining:
            raise EngineDrainingError(
                "engine is draining; restore on another replica")
        rid = snap.rid
        old = self._requests.get(rid)
        if old is not None:
            if not old.done:
                raise ValueError(f"duplicate request id {rid!r}")
            # superseding a finished life of the same rid (see
            # add_request) — a KV_PULL may land on the very replica
            # that ran the prefill phase when the decode role starves
            del self._requests[rid]
        self.admission_check(len(snap.prompt), snap.max_new_tokens)
        self._check_overload_gates(len(snap.prompt), snap.max_new_tokens,
                                   int(tenant), int(priority), None)
        # the payload is usable only in the pool's own storage format
        # (int8 codes+scales vs fp pages have different bytes) and page
        # geometry — a mismatch is a recompute, never a reinterpret
        inject = bool(snap.payloads) and (
            snap.kv_tag == self.pool._tier_tag
            and snap.page_size == self.page_size)
        if inject:
            try:
                _fault.trip("serving.snapshot_restore", step=self._steps,
                            path=rid, poison=snap.corrupt)
            except _fault.FaultInjected:
                inject = False
                self.metrics.counters["snapshot_restore_failed"] += 1
            if inject and not snap.verify():
                # bit rot (or the poison action above) since capture:
                # the digest re-verify catches it HERE, before any byte
                # reaches the pool — fall back to recompute
                inject = False
                self.metrics.counters["snapshot_restore_corrupt"] += 1
        # multi-tenant LoRA: an adapter-bound snapshot restores only on
        # an engine that can actually serve that adapter — unknown here
        # means typed refusal (the router retries elsewhere), never a
        # silent base-model resume. Its injected KV lands under the
        # adapter's prefix-cache namespace, so the re-admission match
        # finds it and a foreign adapter's identical prompt cannot.
        if snap.adapter:
            from .lora import AdapterUnavailableError
            if self.adapters is None:
                raise AdapterUnavailableError(
                    f"snapshot {rid!r} is bound to adapter "
                    f"{snap.adapter[:12]}... but this engine was built "
                    f"without lora=...")
            self.adapters.resolve(snap.adapter)
        if inject:
            self.pool.inject_prefix(snap.seq(), snap.payloads,
                                    namespace=bytes.fromhex(snap.adapter)
                                    if snap.adapter else b"")
        req = Request(rid=rid, prompt=list(snap.prompt),
                      max_new_tokens=snap.max_new_tokens,
                      sampling=SamplingParams(
                          temperature=snap.temperature, top_p=snap.top_p,
                          do_sample=snap.do_sample, seed=snap.seed),
                      eos_token_id=snap.eos_token_id,
                      arrival_t=self.metrics.now(),
                      tenant=int(tenant), priority=int(priority),
                      adapter=snap.adapter)
        req.tokens = list(snap.tokens)
        try:
            self.scheduler.add(req, self.pool)
        except QueueFullError:
            self.metrics.on_reject("queue_full")
            raise
        self._requests[rid] = req
        self.metrics.on_arrival(rid, tenant=int(tenant),
                                priority=int(priority))
        self.metrics.counters["snapshot_restores"] += 1
        self.metrics.counters["snapshot_restored_tokens"] += len(snap.tokens)
        self.tracer.instant("snapshot_restore", track=rid,
                            tokens=len(snap.tokens), injected=int(inject))
        return rid

    def audit_pool(self, check_device: bool = True) -> dict:
        """Run the pool's invariant audit (``KVCachePool.audit``)
        against the scheduler's live block tables — the test-teardown /
        chaos-suite hook proving the engine left the pool consistent."""
        tables = [list(r.pages)
                  for r in self.scheduler.running.values() if r.pages]
        slots = ({slot: r.rid for slot, r in self.scheduler.running.items()}
                 if self._recurrent else None)
        return self.pool.audit(block_tables=tables,
                               check_device=check_device, slots=slots)

    def _capture_requests(self) -> list[RequestSnapshot]:
        """Sealed snapshot of every live request, via ONE batched
        ``export_pages`` device_get across all their pages — host-side,
        outside every compiled program, so ``step_program_counts()``
        never moves. A request whose cache holds nothing yet (still
        queued, or admitted at context 0) gets a meta-only snapshot:
        replay still skips re-emitting its already-delivered tokens."""
        ps = self.page_size
        spans: list[tuple[Request, int]] = []
        flat: list[int] = []
        for r in self.scheduler.live_requests():
            n = 0
            if r.pages and r.context_len > 0:
                n = min(self.pool.pages_for(r.context_len), len(r.pages))
            spans.append((r, n))
            flat.extend(r.pages[:n])
        exported = self.pool.export_pages(flat)
        snaps: list[RequestSnapshot] = []
        i = 0
        for r, n in spans:
            payloads = exported[i:i + n]
            i += n
            q = r.context_len % ps
            if n and q and n == self.pool.pages_for(r.context_len):
                # the tail page holds q valid rows; rows beyond may be
                # stale from allocation — zero them host-side so the
                # payload matches the spill invariant (zeros beyond the
                # partial length) and the digest is deterministic
                tail = payloads[-1]
                for k, a in enumerate(tail):
                    a = np.array(a)
                    a[q:] = 0
                    tail[k] = a
            snaps.append(RequestSnapshot(
                rid=r.rid, prompt=list(r.prompt),
                max_new_tokens=r.max_new_tokens,
                eos_token_id=r.eos_token_id,
                temperature=r.sampling.temperature,
                top_p=r.sampling.top_p,
                do_sample=r.sampling.do_sample,
                seed=r.sampling.seed, arrival_seq=r.arrival_seq,
                tokens=list(r.tokens), context_len=int(r.context_len),
                step=self._steps, kv_tag=self.pool._tier_tag,
                page_size=ps, payloads=payloads,
                adapter=r.adapter).seal())
        return snaps

    def _capture_snapshots(self) -> None:
        """Periodic in-memory capture into the attached SnapshotStore
        (the fleet's bounded-replay source). Put-then-trip: the
        ``serving.snapshot`` fault site's ``poison`` action corrupts
        the JUST-stored snapshot (the restore-side digest re-verify
        must catch it); ``raise`` drops the capture — the previous
        snapshot, or full replay, covers the request."""
        store = self.snapshot_store
        snaps = self._capture_requests()
        if not snaps:
            return
        tr = self.tracer
        for snap in snaps:
            store.put(snap.rid, snap)
            try:
                _fault.trip("serving.snapshot", step=self._steps,
                            path=snap.rid,
                            poison=lambda rid=snap.rid: store.corrupt(rid))
            except _fault.FaultInjected:
                store.drop(snap.rid)
                store.counters["snapshot_failed"] += 1
                continue
            if tr.enabled:
                tr.instant("snapshot", track=snap.rid,
                           tokens=len(snap.tokens),
                           pages=len(snap.payloads))
        store.counters["snapshots_captured"] += 1
        self.metrics.on_snapshot_stats(store.stats())

    # ---- disaggregated prefill/decode serving (SERVING.md
    # "Disaggregated serving") ----

    def take_handoffs(self) -> list[RequestSnapshot]:
        """Drain the handoff outbox: sealed KV exports of prefill-only
        requests whose final chunk completed since the last call. The
        fleet's EngineServer streams each one to the router as an
        epoch-stamped ``KV_OFFER``; a decode-role replica then lands it
        via :meth:`restore_request` (``inject_prefix``)."""
        out, self._handoff_outbox = self._handoff_outbox, []
        return out

    def _capture_handoff(self, req: Request) -> RequestSnapshot:
        """Sealed snapshot of ONE request's finished prompt KV — the
        same HostTier payload format + per-page blake2b digests as
        :meth:`_capture_requests`, exported with one batched
        ``device_get`` outside both compiled programs. Captured at
        final-chunk completion, so ``tokens`` is empty and
        ``context_len`` is the full prompt length: the decode side
        re-admits it as a fresh request whose injected prefix matches
        ``n_valid - 1`` tokens and recomputes exactly one suffix row —
        the row whose sample is the (bitwise-identical) first token."""
        ps = self.page_size
        n = 0
        if req.pages and req.context_len > 0:
            n = min(self.pool.pages_for(req.context_len), len(req.pages))
        payloads = self.pool.export_pages(list(req.pages[:n]))
        q = req.context_len % ps
        if n and q and n == self.pool.pages_for(req.context_len):
            # zero the tail page's stale rows host-side (the spill
            # invariant: zeros beyond the partial length) so the digest
            # is deterministic — same rule as _capture_requests
            tail = payloads[-1]
            for k, a in enumerate(tail):
                a = np.array(a)
                a[q:] = 0
                tail[k] = a
        return RequestSnapshot(
            rid=req.rid, prompt=list(req.prompt),
            max_new_tokens=req.max_new_tokens,
            eos_token_id=req.eos_token_id,
            temperature=req.sampling.temperature,
            top_p=req.sampling.top_p,
            do_sample=req.sampling.do_sample,
            seed=req.sampling.seed, arrival_seq=req.arrival_seq,
            tokens=list(req.tokens), context_len=int(req.context_len),
            step=self._steps, kv_tag=self.pool._tier_tag,
            page_size=ps, payloads=payloads,
            adapter=req.adapter).seal()

    def _handoff_finish(self, req: Request, events: list[dict]) -> None:
        """Final-chunk completion of a prefill-only request: export its
        KV to the handoff outbox INSTEAD of emitting the first token,
        then finish it locally with reason ``"handoff"`` (the router
        treats that as a phase transition, not a terminal event — the
        client stream starts on the decode replica). Capture happens
        BEFORE the scheduler releases the pages; the release itself
        registers the prompt in the local prefix index, so a fallback
        recompute on this replica would still be a full cache hit."""
        snap = self._capture_handoff(req)
        self._handoff_outbox.append(snap)
        self.metrics.counters["handoff_exports"] += 1
        self.metrics.on_prefill_complete(req.rid)
        self.scheduler.finish(req, self.pool, "handoff")
        self.metrics.on_finish(req.rid, "handoff")
        self._trace_finish(req, "handoff")
        if self.snapshot_store is not None:
            self.snapshot_store.drop(req.rid)
        events.append({"rid": req.rid, "token": None, "finished": True,
                       "finish_reason": "handoff"})

    def attach_preemption_guard(self, guard=None):
        """Wire SIGTERM to a graceful drain: with a guard attached,
        ``stream`` / ``run_to_completion`` notice ``guard.preempted``
        at the next step boundary and call ``drain`` — a preempted
        server returns structured retry-elsewhere outcomes instead of
        vanishing mid-decode. Pass an existing
        ``distributed.PreemptionGuard`` or let one be installed."""
        if guard is None:
            from ..distributed import PreemptionGuard
            guard = PreemptionGuard()
        self._guard = guard
        return guard

    def request(self, rid: str) -> Request:
        return self._requests[rid]

    def decode_program_count(self) -> int:
        """Compiled-program count of the 1-token decode step — the
        no-retrace contract says this stays 1 no matter how requests
        churn. The only other program is the ``[max_slots, chunk]``
        mixed step (prefill chunks + speculative verify), counted by
        :meth:`mixed_program_count`; ``step_program_counts`` reports
        every step shape so none hides as an uncounted extra program."""
        return int(self._decode_step._cache_size())

    def mixed_program_count(self) -> int:
        """Compiled-program count of the mixed step: pinned at 1 under
        churn once any prefill chunk or verify has dispatched — chunk
        sizes, accept patterns and prefill/decode composition are array
        values (``n_live``/``forced`` lanes), never shapes."""
        return int(self._mixed_step._cache_size())

    def verify_program_count(self) -> int:
        """Speculative verify rides the mixed program (verify is the
        mixed step with draft rows instead of prompt rows): 0 with
        speculation off, else the mixed-step program count."""
        if self._spec is None:
            return 0
        return self.mixed_program_count()

    def step_program_counts(self) -> dict[str, int]:
        """Per-step-shape compiled-program counts. Every step shape the
        engine can dispatch is first-class here, and the O(1)-programs
        contract says each value stays at most 1 no matter how requests
        churn, prompts chunk, or accept patterns vary (asserted by the
        bench drivers and tests/test_serving_spec.py over churn
        epochs)."""
        return {"decode": int(self._decode_step._cache_size()),
                "mixed": int(self._mixed_step._cache_size())}

    def warm_programs(self, *, decode: bool = True,
                      mixed: bool = True) -> None:
        """Compile the step programs with an all-inactive dispatch
        (every row targets the reserved scratch page 0) so benches and
        profilers can separate compile time from steady-state latency
        without fabricating requests. Idempotent — reuses the jit
        caches; ``step_program_counts()`` reads 1/1 afterwards. A
        disagg prefill specialist warms with ``decode=False`` so the
        phase-split contract (``{"decode": 0, "mixed": 1}``, SERVING.md
        "Disaggregated serving") survives warming."""
        if decode:
            self._call_step(self._decode_step, self._warm_lanes("decode"))
        if mixed:
            self._call_step(self._mixed_step, self._warm_lanes("mixed"))
        # the pool's one compiled writer: the first eviction of a
        # serving window must not compile either
        self.pool.warm_scrub()
        self._note_retraces()

    def _warm_lanes(self, program: str) -> tuple:
        """All-inactive lanes of one step program (every row targets the
        reserved scratch page 0): the shapes and dtypes every real
        dispatch has, writing and registering nothing."""
        S, M, K = self.max_slots, self.max_pages_per_slot, self._chunk
        zi = jnp.zeros((S,), jnp.int32)
        zb = jnp.zeros((S,), bool)
        ones = jnp.ones((S,), jnp.float32)
        gt = jnp.ones((S,), bool)
        tables = jnp.zeros((S, M), jnp.int32)
        if program == "decode":
            return (zi, tables, zi, zb, ones, ones, gt, zi, zi,
                    *self._lora_args())
        return (jnp.zeros((S, K), jnp.int32),
                tables, zi, zb, zi, zb, ones, ones, gt, zi, zi,
                *self._lora_args())

    def _warm_args(self, program: str) -> tuple:
        """Every argument of one step program for an all-inactive
        dispatch: the weights, the page arrays, for a model that takes
        a ``HybridCache`` the per-slot state (a latent model's is
        empty), then the lanes."""
        lead = ((self._state, self.pool.pools, self.pool.state)
                if self._hybrid else (self._state, self.pool.pools))
        return (*lead, *self._warm_lanes(program))

    def lower_step_programs(self) -> dict:
        """jax AOT view of the two step programs at the shapes every
        dispatch uses, without running them:
        ``{"decode": Lowered, "mixed": Lowered}``. ``.compile()`` gives
        ``as_text()`` (is the paged-attention kernel in the decode step)
        and ``memory_analysis()`` (does the step fit beside the pool; is
        the donated pool aliased to its result: ``alias_size_in_bytes``).
        Nothing is consumed and ``step_program_counts()`` is not changed
        by it."""
        return {"decode": self._decode_step.lower(*self._warm_args("decode")),
                "mixed": self._mixed_step.lower(*self._warm_args("mixed"))}

    def pipeline_bubble_frac(self, waves: int | None = None) -> float:
        """Idle-stage fraction of the pipelined mixed step: a ring of
        ``pp`` stages over ``W`` waves runs ``W + pp - 1`` ticks of
        which ``pp - 1`` are fill/drain — the bubble is
        ``(pp - 1) / (W + pp - 1)``. At ``waves == 1`` (the unwaved,
        naive sequential schedule) this is ``(pp - 1) / pp``;
        microbatching with ``waves == pp`` shrinks it to
        ``(pp - 1) / (2 pp - 1)`` — strictly below. 0.0 when pp=1."""
        if self.pp <= 1:
            return 0.0
        W = int(waves) if waves is not None else self._pp_waves
        return (self.pp - 1) / (W + self.pp - 1)

    def stats(self) -> dict:
        return {"steps": self._steps,
                "pool": self.pool.stats(),
                "queue_depth": self.scheduler.queue_depth,
                "running": len(self.scheduler.running),
                "preemptions": self.scheduler.num_preemptions,
                "draining": self._draining,
                "decode_programs": self.decode_program_count(),
                "step_programs": self.step_program_counts(),
                # the pow2 bucket family is gone: every prefill token
                # flows through the ONE mixed program
                "prefill_programs": self.mixed_program_count(),
                "prefix_cache": self.prefix_cache,
                "recurrent_state": self._recurrent,
                "latent_cache": self._latent,
                "kv_quant": self.kv_quant,
                "host_tier": self.pool.host_tier is not None,
                "speculative": self._spec is not None,
                "chunked": self.chunked,
                "prefill_chunk": self.prefill_chunk,
                "snapshots": self.snapshot_store is not None,
                "snapshot_interval": self.snapshot_interval,
                "tp": self.tp,
                "pp": self.pp,
                "pp_waves": self._pp_waves,
                "pipeline_bubble_frac": self.pipeline_bubble_frac(),
                "fair": self.scheduler.fair,
                "brownout": self._brownout is not None,
                "brownout_level": self._brownout_level,
                "lora": (self.adapters.stats()
                         if self.adapters is not None else None),
                "tracing": self.tracer.enabled}

    @property
    def brownout_level(self) -> int:
        """Current brownout ladder level (0 = normal service)."""
        return self._brownout_level

    # ------------------------------------------------------------------
    # robustness internals
    # ------------------------------------------------------------------

    def _preemption_pending(self) -> bool:
        return (self._guard is not None and self._guard.preempted
                and not self._draining)

    def _trace_finish(self, req: Request, reason: str | None) -> None:
        """Request-track terminal marker (the scheduler already closed
        the request's queued/running span)."""
        tr = self.tracer
        if tr.enabled:
            tr.instant("finish", track=req.rid, reason=reason or "",
                       tokens=len(req.tokens))
            tr.bump("finishes")

    def _dump_flight(self, reason: str, snapshot: dict | None = None):
        """Auto-dump the attached flight recorder at a terminal
        condition; returns the dump path (None without a recorder — and
        an unwritable destination never masks the original failure)."""
        if self.flight_recorder is None:
            return None
        try:
            return self.flight_recorder.dump(reason, snapshot=snapshot)
        except OSError:
            return None

    def _expire_deadlines(self, events: list[dict]) -> None:
        """Step-boundary deadline enforcement on the injectable metrics
        clock: a waiting request past max_queue_wait_s (or its overall
        deadline_s) and a running request past deadline_s both finish
        with ``finish_reason="timeout"``."""
        now = self.metrics.now()
        for req in list(self.scheduler.waiting):
            waited = now - req.arrival_t
            if ((req.deadline_s is not None and waited >= req.deadline_s)
                    or (req.max_queue_wait_s is not None
                        and waited >= req.max_queue_wait_s)):
                self._finish_abnormal(req, "timeout", events)
        for req in list(self.scheduler.running.values()):
            if (req.deadline_s is not None
                    and now - req.arrival_t >= req.deadline_s):
                self._finish_abnormal(req, "timeout", events)

    def _update_brownout(self, events: list[dict]) -> None:
        """One hysteresis tick of the brownout ladder (see
        :class:`BrownoutConfig`): escalate one level when the queue is
        over the high watermark (depth, or oldest queued wait), step
        back down one level when it is under the low watermark, and
        never move twice within ``dwell_steps`` — a single-step spike
        cannot flap the ladder. Level 3 sheds lowest-priority queued
        requests here. Pure host-side policy: transitions change a
        budget scalar, a drafter skip, and queue membership — never a
        compiled shape, so ``step_program_counts()`` is pinned across
        every transition."""
        cfg = self._brownout
        now = self.metrics.now()
        depth = self.scheduler.queue_depth
        head_wait = max((now - r.arrival_t
                         for r in self.scheduler.waiting), default=0.0)
        hot = depth >= cfg.high_queue or (
            cfg.high_wait_s is not None and head_wait >= cfg.high_wait_s)
        cool = depth <= cfg.low_queue and (
            cfg.low_wait_s is None or head_wait <= cfg.low_wait_s)
        level = self._brownout_level
        if self._steps - self._brownout_since >= cfg.dwell_steps:
            new = level
            if hot and level < 3:
                new = level + 1
            elif cool and level > 0:
                new = level - 1
            if new != level:
                self._brownout_level = new
                self._brownout_since = self._steps
                self.metrics.on_brownout_transition(level, new)
                self.tracer.instant("brownout", level=new, queue=depth)
                # chaos site: a fault here models the overload
                # controller crashing mid-transition (path "old->new")
                _fault.trip("serving.brownout", step=self._steps,
                            path=f"{level}->{new}")
        if self._brownout_level >= 3:
            self._shed_queued(events)
        self.metrics.on_brownout_level(self._brownout_level)

    def _shed_queued(self, events: list[dict]) -> None:
        """Brownout level 3: shed the lowest-priority queued requests
        (youngest first within a priority class — the oldest work is
        closest to its SLO and is spared longest) until the queue is
        back at the high watermark. ``finish_reason="shed"`` is
        terminal on THIS engine but retryable fleet-wide — the
        router's shed events carry ``retry_after_s``."""
        cfg = self._brownout
        while self.scheduler.queue_depth > cfg.high_queue:
            victim = min(self.scheduler.waiting,
                         key=lambda r: (r.priority, -r.arrival_seq))
            self.metrics.on_shed(victim.tenant, victim.priority)
            self.tracer.instant("brownout_shed", track=victim.rid,
                                priority=victim.priority)
            self._finish_abnormal(victim, "shed", events)

    def _flush_waiting(self, events: list[dict]) -> None:
        """Draining: nothing waits — evict the queue as retriable
        ``preempted`` outcomes (covers preemption requeues mid-drain)."""
        for req in list(self.scheduler.waiting):
            self._finish_abnormal(req, "preempted", events)

    def _finish_abnormal(self, req: Request, reason: str,
                         events: list[dict]) -> None:
        if reason == "nonfinite":
            # poison containment: deregister the pages from the prefix
            # index NOW (no future request may match NaN content) and
            # mark them scrub-on-zero. The scrub itself happens when the
            # last reference drops — pages shared with a live request
            # are never zeroed under the reader; pages this request
            # holds alone are scrubbed as its release lands. (A NaN left
            # behind would break the pool's masked-garbage-is-exact-zero
            # invariant: additive masking cannot silence a NaN —
            # NaN + -1e30 is still NaN.)
            self.pool.quarantine(req.pages)
            self._dump_flight("nonfinite", {"rid": req.rid,
                                            "step": self._steps})
        self.scheduler.finish(req, self.pool, reason)
        self.metrics.on_outcome(reason)
        self.metrics.on_finish(req.rid, reason)
        self._trace_finish(req, reason)
        if self.snapshot_store is not None and reason != "preempted":
            # terminal here AND fleet-wide — but a "preempted" eviction
            # is retry-elsewhere, and its snapshot is exactly what lets
            # the retry be a bounded replay instead of a full one
            self.snapshot_store.drop(req.rid)
        events.append({"rid": req.rid, "token": None, "finished": True,
                       "finish_reason": reason})

    def _scrub_pages(self, pages: list[int]) -> None:
        self.pool.scrub(pages)

    def _poison_pages(self, req: Request) -> None:
        """Fault-action callback (``action="poison"``): NaN the
        request's LAST KV page in layer 0 — its next decode step reads
        the NaN through its own block table (additive masking cannot
        silence a NaN) and its logits go non-finite, while no other
        slot can see the page. The last page — not the first: under
        prefix caching the leading pages may be SHARED cached pages,
        and poisoning one would blast every request mapping it. The
        trailing page is never in the prefix index while its owner
        runs (only full prompt pages are registered at the final
        chunk; the partial tail waits for release), so it is always
        private.

        Only kv head 0 is poisoned — under TP that head lives on ONE
        shard, modelling single-device corruption in a TP group; the
        NaN still reaches every slot output (o_proj mixes all query
        heads, and at tp>1 the attention-block psum broadcasts it to
        every shard), so the quarantine is fleet-wide either way."""
        if not req.pages:
            return
        page = req.pages[-1]
        pk, *pv = self.pool.pools[0]     # a latent pool's entry is (rows,)
        from ..quantization.serving import QuantizedKV
        if self.pool.stacked:
            # pp pool: pools[0] is the stacked [L, pages, ...] pair —
            # poison layer 0 of the page (stage 0's slice; the NaN
            # still reaches every stage through the activation ring)
            if isinstance(pk, QuantizedKV):
                pk = QuantizedKV(pk.q,
                                 pk.scale.at[0, page, :, 0].set(jnp.nan))
            else:
                pk = pk.at[0, page, :, 0].set(jnp.nan)
            self.pool.pools[0] = (pk, *pv)
            return
        if isinstance(pk, QuantizedKV):
            # int8 codes cannot hold a NaN — poison the page's fp32
            # SCALE row instead: NaN * code propagates through the
            # dequant into the attention output exactly like a poisoned
            # fp page (and the quarantine scrub must therefore zero
            # scales as well as codes — tested in test_serving_quant)
            self.pool.pools[0] = (
                QuantizedKV(pk.q, pk.scale.at[page, :, 0].set(jnp.nan)),
                *pv)
        else:
            self.pool.pools[0] = (pk.at[page, :, 0].set(jnp.nan), *pv)

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------

    def _lora_args(self, atable=None) -> tuple:
        """Trailing step-program args when LoRA serving is on: the
        ``[max_slots]`` adapter-table lane (slot -> AdapterPool slot)
        plus the pool's padded device buffers. Empty tuple when off, so
        the base engine's call signature — and compiled program — is
        byte-identical to the pre-LoRA engine."""
        if self.adapters is None:
            return ()
        if atable is None:
            atable = np.zeros((self.max_slots,), np.int32)
        return (jnp.asarray(atable, jnp.int32), self.adapters.buffers())

    def _slot_atable(self) -> np.ndarray:
        """The adapter-table lane for the CURRENT running set (0 for
        free slots — the identity adapter)."""
        atable = np.zeros((self.max_slots,), np.int32)
        for slot, req in self.scheduler.running.items():
            atable[slot] = req.adapter_slot
        return atable

    def _build_decode_step(self):
        from ..nn.module import functional_call
        model = self.model

        def decode_step(state, pools, tok, tables, seq_lens, active,
                        temps, top_ps, greedy, seeds, counts,
                        atable=None, lbuf=None):
            # multi-tenant LoRA: atable is the [max_slots] adapter-table
            # lane (slot -> AdapterPool slot; 0 = identity) and lbuf the
            # pool's padded A/B buffers + scales. A lora engine passes
            # them on EVERY call, a base engine never does — either way
            # one treedef, one compiled program.
            lora = None if lbuf is None else (atable, lbuf[0], lbuf[1])
            (logits, pools), _ = functional_call(
                model, state, tok[:, None], None, pools, 0,
                (tables, seq_lens, active), lora=lora, training=False)
            last = logits[:, -1]
            # per-slot poison sentinel: rows are independent, so a
            # non-finite row indicts exactly one slot
            ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)), axis=-1)
            with jax.named_scope("sampler"):
                nt = _sample_rows(last, temps, top_ps, greedy, seeds, counts)
            return nt, ok, pools

        # every body donates the page pairs: the scatter of this step's
        # rows updates the arrays it was given, and ``_call_step`` puts
        # the returned ones in their place
        if self._hybrid:
            # the same body with the per-slot state threaded beside the
            # pages and donated with them (the step rewrites every
            # slot's row, so the new state takes the old one's memory;
            # a latent model's state is empty); the model's own counters
            # ride back with it
            def decode_step_state(state, pools, rstate, tok, tables,
                                  seq_lens, active, temps, top_ps, greedy,
                                  seeds, counts):
                nt, ok, cache = decode_step(
                    state, HybridCache(pools, rstate), tok, tables,
                    seq_lens, active, temps, top_ps, greedy, seeds, counts)
                return nt, ok, cache.kv, cache.state, cache.counts
            return jax.jit(decode_step_state, donate_argnums=(1, 2))
        if self._tp is None:
            return jax.jit(decode_step, donate_argnums=(1,))
        tp = self._tp
        if tp.pp > 1:
            # pp>1: the forward routes through the staged pipeline ring
            # (TPContext.staged_forward, one wave — decode is a single
            # row per slot) instead of the flat model; the sampling tail
            # is byte-identical, running on the replicated post-gather
            # logits, so the fold_in contract and bitwise parity vs the
            # tp-only engine hold
            def decode_step_pp(state, pools, tok, tables, seq_lens,
                               active, temps, top_ps, greedy, seeds,
                               counts):
                logits, pools = tp.staged_forward(
                    state, pools, tok[:, None], tables, seq_lens, active,
                    None, waves=1)
                last = logits[:, -1]
                ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)),
                             axis=-1)
                with jax.named_scope("sampler"):
                    nt = _sample_rows(last, temps, top_ps, greedy, seeds,
                                      counts)
                return nt, ok, pools
            return tp.compile_step(decode_step_pp, self._state,
                                   self.pool.pools, n_lanes=9, n_lead=2)
        # tp>1: the SAME body compiles as ONE shard_map program over the
        # mp axis — state/pools come in sharded, the 9 host-built lanes
        # replicated, tokens/ok out replicated (sampling ran on the
        # all-gathered logits, identically on every shard)
        return self._tp.compile_step(decode_step, self._state,
                                     self.pool.pools, n_lanes=9, n_lead=2)

    def _build_mixed_step(self):
        """THE mixed step: ONE fixed-shape ``[max_slots, chunk]``
        program for the engine's lifetime, serving prefill chunks,
        decode, speculative verify, and any per-slot mixture.

        Per slot, ``n_live`` new tokens start at pool position
        ``seq_lens``: row j is written at ``seq_lens + j`` and attends
        causally up to itself (rows >= n_live and inactive slots write
        scratch page 0). Two slot flavors share the shape:

        - ``forced`` (a prefill chunk): the rows are the next n_live
          prompt tokens, teacher-forced — every row is accepted by
          construction (``m = n_live - 1``) and only the LAST row's
          sample can matter (the first token of a fresh request's
          stream, on its final chunk);
        - verify/decode (not forced): row 0 is the ordinary decode
          input (the last generated token) and rows 1..n_live-1 are
          the drafter's guesses; draft row j is ACCEPTED iff it equals
          the row j-1 sample, the Leviathan accept/reject rule.

        Only the rows whose sample can be emitted are sampled
        (``_mixed_tail``): ``spec_k`` rows a slot, a chunk slot's last
        live row in column 0 and a verify slot's rows ``0..spec_k-1``,
        so ``samp`` is ``[max_slots, spec_k]``. Each keeps the engine's
        standard contract — ``fold_in(PRNGKey(seed), counts + j)`` with
        the row's own index j, the exact key the sequential engine
        would use for that token index — so emitted streams are bitwise
        identical to sequential decode (greedy and sampled) no matter
        how prompts chunk or what the drafter proposed. Rejected live
        rows are zeroed IN-PROGRAM (fixed-shape scatter: rejected rows
        target their real (page, offset), everything else targets
        scratch (0, 0)) so no garbage outlives the step — chunk sizes
        and accept patterns are data, never shapes."""
        from ..nn.module import functional_call
        model = self.model
        ps = self.page_size
        R = self.scheduler.spec_k

        def mixed_step(state, pools, toks, tables, seq_lens, active,
                       n_live, forced, temps, top_ps, greedy, seeds,
                       counts, atable=None, lbuf=None):
            lora = None if lbuf is None else (atable, lbuf[0], lbuf[1])
            (logits, pools), _ = functional_call(
                model, state, toks, None, pools, 0,
                (tables, seq_lens, active, n_live), lora=lora,
                training=False)
            return _mixed_tail(logits, pools, toks, tables, seq_lens,
                               active, n_live, forced, temps, top_ps,
                               greedy, seeds, counts, R, ps, False)

        if self._hybrid:
            def mixed_step_state(state, pools, rstate, toks, tables,
                                 seq_lens, active, n_live, forced, temps,
                                 top_ps, greedy, seeds, counts):
                (logits, cache), _ = functional_call(
                    model, state, toks, None, HybridCache(pools, rstate),
                    0, (tables, seq_lens, active, n_live), training=False)
                # no row is ever rejected (speculation is refused), so
                # the tail's rollback zeroes nothing: of the pages only
                samp, m, ok, kv = _mixed_tail(
                    logits, cache.kv, toks, tables, seq_lens, active,
                    n_live, forced, temps, top_ps, greedy, seeds, counts,
                    R, ps, False)
                return samp, m, ok, kv, cache.state, cache.counts
            return jax.jit(mixed_step_state, donate_argnums=(1, 2))
        if self._tp is None:
            return jax.jit(mixed_step, donate_argnums=(1,))
        tp = self._tp
        if tp.pp > 1:
            # pp>1: the forward is the microbatched pipeline ring — the
            # chunk splits into waves that overlap across stages — and
            # everything after the logits is the tp body's own tail on
            # the replicated values, except the rollback scatter
            # addresses the stacked [L, pages, ...] pool layout
            waves = self._pp_waves

            def mixed_step_pp(state, pools, toks, tables, seq_lens,
                              active, n_live, forced, temps, top_ps,
                              greedy, seeds, counts):
                logits, pools = tp.staged_forward(
                    state, pools, toks, tables, seq_lens, active, n_live,
                    waves=waves)
                return _mixed_tail(logits, pools, toks, tables, seq_lens,
                                   active, n_live, forced, temps, top_ps,
                                   greedy, seeds, counts, R, ps, True)
            return tp.compile_step(mixed_step_pp, self._state,
                                   self.pool.pools, n_lanes=11, n_lead=3)
        # tp>1: same body, ONE shard_map program (the rollback scatter is
        # head-local — page/off index the replicated dims, every shard
        # zeroes its own kvh/tp heads of the rejected rows)
        return self._tp.compile_step(mixed_step, self._state,
                                     self.pool.pools, n_lanes=11, n_lead=3)

    # ------------------------------------------------------------------
    # per-step work
    # ------------------------------------------------------------------

    def _run_prefill(self, req: Request, events: list[dict]) -> None:
        """Unchunked (``chunked=False``) admission prefill: run the
        whole uncached suffix through the mixed program NOW, inside the
        admission loop, as forced single-slot passes of up to ``chunk``
        rows each. This is the legacy whole-prompt pacing (the A/B
        baseline arm): registration and first-token emission complete
        before the next admission's prefix lookup, so a same-step burst
        sharing a system prompt still prefills the common prefix
        exactly once — but the step's decode slots wait for the whole
        prompt, which is exactly the head-of-line blocking chunked mode
        removes."""
        tr = self.tracer
        n_valid = req.prefill_target
        cached = req.cached_len
        n_sfx = n_valid - cached
        seq = req.prompt + req.tokens[:-1]
        if n_sfx == 0:
            tr.instant("prefill_cached", track=req.rid, cached=cached)
            # recompute fully served from the prefix cache: the pages
            # already hold the materialized context bit-for-bit and the
            # recompute prefill's prediction would be discarded anyway —
            # no program runs, the stored last token drives the next
            # decode step. (Only reachable for req.tokens non-empty:
            # fresh admissions cap the match at n_valid - 1.)
            return
        S, M, K = self.max_slots, self.max_pages_per_slot, self._chunk
        slot = req.slot
        sp = req.sampling
        tok = 0
        ok_all = True
        with tr.span("prefill", track=req.rid, cached=cached,
                     suffix=n_sfx, chunks=-(-n_sfx // K)):
            start = cached
            while start < n_valid:
                n = min(K, n_valid - start)
                toks = np.zeros((S, K), np.int32)
                toks[slot, :n] = seq[start:start + n]
                tables = np.zeros((S, M), np.int32)
                tables[slot, :len(req.pages)] = req.pages
                seq_lens = np.zeros((S,), np.int32)
                seq_lens[slot] = start
                active = np.zeros((S,), bool)
                active[slot] = True
                n_live = np.zeros((S,), np.int32)
                n_live[slot] = n
                forced = np.zeros((S,), bool)
                forced[slot] = True
                temps = np.ones((S,), np.float32)
                temps[slot] = sp.temperature
                top_ps = np.ones((S,), np.float32)
                top_ps[slot] = sp.top_p
                greedy = np.ones((S,), bool)
                greedy[slot] = not sp.do_sample
                seeds = np.zeros((S,), np.int32)
                seeds[slot] = sp.seed
                counts = np.zeros((S,), np.int32)
                # row j samples with counts + j: anchor the LAST row of
                # the pass, the one the program samples for a forced
                # slot, on this request's next token index
                counts[slot] = len(req.tokens) - (n - 1)
                atable = np.zeros((S,), np.int32)
                atable[slot] = req.adapter_slot
                tr.bump("rows_sampled", S * self.scheduler.spec_k)
                samp, _, ok = self._call_step(self._mixed_step, (
                    jnp.asarray(toks),
                    jnp.asarray(tables), jnp.asarray(seq_lens),
                    jnp.asarray(active), jnp.asarray(n_live),
                    jnp.asarray(forced), jnp.asarray(temps),
                    jnp.asarray(top_ps), jnp.asarray(greedy),
                    jnp.asarray(seeds), jnp.asarray(counts),
                    *self._lora_args(atable)))
                samp, ok = self._watched_sync(samp, ok)
                start += n
                tok = int(samp[slot, 0])  # the pass's last live row
                if not bool(ok[slot]):
                    ok_all = False
                    break  # NaN cache rows only propagate — stop early
        if self.kv_quant:
            # quantize-at-scatter observability: error-stat gauge (per-
            # element error <= scale/2) + one trace instant per prefill
            qs = self._qscale_max(req.pages)
            self.metrics.on_kv_quant_scale(qs)
            tr.instant("kv_quantize", track=req.rid,
                       scale_max=round(qs, 6), suffix=n_sfx)
        if _fault.active_plan() is not None:
            try:
                _fault.trip("serving.prefill", step=self._steps,
                            path=req.rid,
                            poison=lambda r=req: self._poison_pages(r))
            except _fault.FaultInjected:
                self._finish_abnormal(req, "injected", events)
                return
        if not ok_all:
            # the prompt itself produced non-finite logits — quarantine
            # at admission, before it ever joins the decode batch
            self._finish_abnormal(req, "nonfinite", events)
            return
        # index the prompt's full pages NOW (not at release) so requests
        # arriving while this one is still decoding can already share
        # its prefix — the staggered shared-system-prompt workload. Full
        # pages are immutable from here on; the trailing partial page
        # keeps filling during decode and is registered at release.
        self.pool.register_prefix(seq[:n_valid], req.pages,
                                  include_partial=False,
                                  namespace=req.adapter_ns)
        if req.tokens:
            return  # recompute after preemption: cache rebuilt, the stored
                    # last token is the next decode input — no new emission
        if req.handoff:
            # disaggregated serving (unchunked arm): same publish-
            # instead-of-emit rule as the mixed-step final chunk
            self._handoff_finish(req, events)
            return
        self._emit(req, tok, events)

    def _qscale_max(self, pages: list[int]) -> float:
        """Max absmax scale over the request's pages across all layers
        — the bounded-dequant-error stat (per-element error <= scale/2)
        the metrics gauge and ``kv_quantize`` trace instants report."""
        idx = jnp.asarray(pages, jnp.int32)
        qs = 0.0
        for pk, pv in self.pool.pools:
            qs = max(qs, float(jnp.max(pk.scale[idx])),
                     float(jnp.max(pv.scale[idx])))
        return qs

    def _plan_chunks(self, budget: int) -> dict[int, int]:
        """slot -> n_new: this step's prefill chunks, FCFS by arrival
        over the partially-prefilled slots under the remaining prefill
        token budget. The OLDEST prefilling slot always advances at
        least one token even with the budget exhausted (chunked
        admission charges no suffix, so this is the no-starvation
        guarantee that keeps stall detection honest); younger slots
        never jump the budget queue."""
        plan: dict[int, int] = {}
        if not self.chunked:
            return plan
        C = self._chunk
        Kw = C // max(self.scheduler.pp_waves, 1)
        prefilling = sorted(
            ((slot, req) for slot, req in self.scheduler.running.items()
             if req.prefilling),
            key=lambda sr: sr[1].arrival_seq)
        for slot, req in prefilling:
            need = req.prefill_target - req.context_len
            cap = budget if plan else max(budget, 1)
            n = min(C, need, cap)
            if n <= 0:
                break
            if n < need and n > Kw:
                # wave alignment (pp microbatching): a non-final bite
                # rounds down to whole waves of the microbatched mixed
                # step, so no wave runs half-empty mid-prompt. Pure
                # pacing — chunk boundaries never change the emitted
                # stream (chunked-prefill parity contract). At
                # pp_waves=1, Kw == C >= n and this never fires.
                n = (n // Kw) * Kw
            plan[slot] = n
            budget -= n
        return plan

    def _run_batch(self, events: list[dict], budget: int) -> tuple[int, str]:
        """Dispatch this step's model work: plan prefill chunks under
        the remaining token budget, then route — any chunk or draft
        rows go through the ONE mixed program (decode slots ride along
        in the same dispatch); a pure-decode step keeps the cheap
        ``[max_slots]`` decode program. Returns the number of prefill
        chunk tokens dispatched (progress accounting for the stall
        detector) and which program ran (``decode`` | ``mixed`` |
        ``none``)."""
        with self.tracer.span("plan"):
            if _fault.active_plan() is not None:
                for req in list(self.scheduler.running.values()):
                    if req.prefilling:
                        continue  # serving.prefill trips at chunk dispatch
                    try:
                        _fault.trip(
                            "serving.decode", step=self._steps, path=req.rid,
                            poison=lambda r=req: self._poison_pages(r))
                    except _fault.FaultInjected:
                        self._finish_abnormal(req, "injected", events)
                if not self.scheduler.running:
                    return 0, "none"
            plan = self._plan_chunks(budget)
            has_drafts = self._spec is not None and any(
                req.draft_tokens for req in self.scheduler.running.values())
        if plan or has_drafts:
            return self._run_mixed(events, plan), "mixed"
        self._run_decode(events)
        return 0, "decode"

    def _run_decode(self, events: list[dict]) -> None:
        tr = self.tracer
        S, M = self.max_slots, self.max_pages_per_slot
        with tr.span("build_inputs"):
            tok = np.zeros((S,), np.int32)
            tables = np.zeros((S, M), np.int32)
            seq_lens = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            temps = np.ones((S,), np.float32)
            top_ps = np.ones((S,), np.float32)
            greedy = np.ones((S,), bool)
            seeds = np.zeros((S,), np.int32)
            counts = np.zeros((S,), np.int32)
            for slot, req in self.scheduler.running.items():
                tok[slot] = req.tokens[-1]
                tables[slot, :len(req.pages)] = req.pages
                seq_lens[slot] = req.context_len
                active[slot] = True
                temps[slot] = req.sampling.temperature
                top_ps[slot] = req.sampling.top_p
                greedy[slot] = not req.sampling.do_sample
                seeds[slot] = req.sampling.seed
                counts[slot] = len(req.tokens)
            lanes = (jnp.asarray(tok), jnp.asarray(tables),
                     jnp.asarray(seq_lens), jnp.asarray(active),
                     jnp.asarray(temps), jnp.asarray(top_ps),
                     jnp.asarray(greedy), jnp.asarray(seeds),
                     jnp.asarray(counts),
                     *self._lora_args(self._slot_atable()))
            if tr.enabled:
                # the sampler's and the attention core's padding, counted
                # where the work is handed over: rows the program samples
                # against tokens emitted, block-table entries the kernel's
                # grid walks against the pages that hold the keys each
                # decoding slot attends (its context and the new token)
                ps = self.page_size
                tr.bump("decode_steps")
                tr.bump("rows_sampled", S)
                tr.bump("table_entries_dispatched",
                        M * len(self.scheduler.running))
                tr.bump("table_entries_live",
                        sum(req.context_len // ps + 1
                            for req in self.scheduler.running.values()))
        with tr.span("decode_dispatch", slots=len(self.scheduler.running)):
            nt, ok = self._call_step(self._decode_step, lanes)
        nt, ok = self._watched_sync(nt, ok)
        if self._hybrid and tr.enabled:
            self._bump_hybrid_counters()
        with tr.span("sample_emit"):
            for slot, req in list(self.scheduler.running.items()):
                req.context_len += 1  # this step's KV write at old
                                      # context_len
                if not ok[slot]:
                    # poison quarantine: only this slot finishes;
                    # survivors' rows were computed independently and
                    # stay bitwise intact
                    self._finish_abnormal(req, "nonfinite", events)
                    continue
                self._emit(req, int(nt[slot]), events)

    def _run_mixed(self, events: list[dict], plan: dict[int, int]) -> int:
        """One mixed dispatch: the planned prefill chunks (teacher-
        forced prompt rows) and every decoding slot (decode input +
        drafts) share the fixed-shape ``[max_slots, chunk]`` program.
        Chunk slots advance ``context_len`` and emit only on their
        FINAL chunk — which is also when the prompt's full pages commit
        to the prefix index (first-writer-wins; a request preempted
        mid-prompt registers nothing). Decode slots emit their accepted
        sample prefix plus the bonus correction sample — bitwise the
        tokens sequential decode would have produced."""
        tr = self.tracer
        sched = self.scheduler
        # the plan may be stale by one preemption (ensure_decode_pages
        # ran in between) — keep only slots that still owe chunks
        plan = {slot: n for slot, n in plan.items()
                if slot in sched.running and sched.running[slot].prefilling}
        with tr.span("build_inputs"):
            lanes, n_drafted, chunk_tokens = self._mixed_lanes(plan)
            self.metrics.on_mixed_step(
                chunk_tokens, len(n_drafted), len(plan),
                sum(1 for r in sched.running.values() if r.prefilling))
            if tr.enabled:
                # rows the program samples, against tokens emitted
                tr.bump("mixed_steps")
                tr.bump("rows_sampled", self.max_slots * sched.spec_k)
        with tr.span("mixed_dispatch", slots=len(plan) + len(n_drafted),
                     chunk_tokens=chunk_tokens,
                     drafts=sum(n_drafted.values())):
            samp, acc, ok = self._call_step(self._mixed_step, lanes)
        samp, acc, ok = self._watched_sync(samp, acc, ok)
        if self._hybrid and tr.enabled:
            self._bump_hybrid_counters()
        with tr.span("sample_emit"):
            self._mixed_emit(events, plan, n_drafted, samp, acc, ok)
        return chunk_tokens

    def _mixed_lanes(self, plan: dict[int, int]):
        """The mixed program's host-built lanes for this step, as device
        arrays; the drafts per verify slot and the chunk tokens planned."""
        tr = self.tracer
        sched = self.scheduler
        S, M, K = self.max_slots, self.max_pages_per_slot, self._chunk
        toks = np.zeros((S, K), np.int32)
        tables = np.zeros((S, M), np.int32)
        seq_lens = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        n_live = np.zeros((S,), np.int32)
        forced = np.zeros((S,), bool)
        temps = np.ones((S,), np.float32)
        top_ps = np.ones((S,), np.float32)
        greedy = np.ones((S,), bool)
        seeds = np.zeros((S,), np.int32)
        counts = np.zeros((S,), np.int32)
        n_drafted: dict[int, int] = {}
        chunk_tokens = 0
        for slot, req in sched.running.items():
            if req.prefilling and slot not in plan:
                continue  # out of budget this step: the slot sits out
            sp = req.sampling
            tables[slot, :len(req.pages)] = req.pages
            seq_lens[slot] = req.context_len
            active[slot] = True
            temps[slot] = sp.temperature
            top_ps[slot] = sp.top_p
            greedy[slot] = not sp.do_sample
            seeds[slot] = sp.seed
            if slot in plan:
                n = plan[slot]
                seq = req.prompt + req.tokens[:-1]
                toks[slot, :n] = seq[req.context_len:req.context_len + n]
                n_live[slot] = n
                forced[slot] = True
                # row j samples with counts + j: anchor the LAST chunk
                # row, the one the program samples for a forced slot,
                # on this request's next token index
                counts[slot] = len(req.tokens) - (n - 1)
                chunk_tokens += n
                if tr.enabled:
                    tr.instant("chunk", track=req.rid,
                               start=int(req.context_len), n=n)
                    tr.bump("chunks")
            else:
                d = req.draft_tokens
                toks[slot, 0] = req.tokens[-1]
                if d:
                    toks[slot, 1:1 + len(d)] = d
                n_live[slot] = 1 + len(d)
                n_drafted[slot] = len(d)
                counts[slot] = len(req.tokens)
        if tr.enabled and self._latent:
            # the attention core's padding, counted where the work is
            # handed over: the grid steps a layer that the rows kernel
            # walks over these lanes, against those that compute (the
            # kernel's own predicate, by the module that holds it)
            live, dispatched = latent_grid_steps(
                seq_lens, n_live, rows=K, max_pages=M,
                heads=self.model.config.num_attention_heads,
                page_size=self.page_size)
            tr.bump("latent_steps_live", live)
            tr.bump("latent_steps_dispatched", dispatched)
        if tr.enabled and self._recurrent:
            # the recurrent layers' padding: the rows of these lanes that
            # carry a token (what a scan over the live rows walks)
            # against the rows of the rectangle, in every layer that
            # keeps a state
            layers = len(self.pool.state)
            tr.bump("scan_rows_live", int(n_live.sum()) * layers)
            tr.bump("scan_rows_dispatched", S * K * layers)
        lanes = (jnp.asarray(toks), jnp.asarray(tables),
                 jnp.asarray(seq_lens), jnp.asarray(active),
                 jnp.asarray(n_live), jnp.asarray(forced),
                 jnp.asarray(temps), jnp.asarray(top_ps),
                 jnp.asarray(greedy), jnp.asarray(seeds),
                 jnp.asarray(counts), *self._lora_args(self._slot_atable()))
        return lanes, n_drafted, chunk_tokens

    def _mixed_emit(self, events, plan, n_drafted, samp, acc, ok) -> None:
        """What the mixed program returned, taken slot by slot: chunk
        slots advance (and emit on their final chunk), verify/decode
        slots emit their accepted prefix plus the bonus sample."""
        tr = self.tracer
        sched = self.scheduler
        # serving.prefill fault trips for the chunk slots, mirroring
        # the legacy prefill site: after the write, before the ok check
        # and before any registration — an injected chunk failure can
        # never index its pages
        if _fault.active_plan() is not None:
            for slot in list(plan):
                req = sched.running.get(slot)
                if req is None:
                    continue
                try:
                    _fault.trip("serving.prefill", step=self._steps,
                                path=req.rid,
                                poison=lambda r=req: self._poison_pages(r))
                except _fault.FaultInjected:
                    req.context_len += plan.pop(slot)
                    self._finish_abnormal(req, "injected", events)
        participants = ([s for s in plan if s in sched.running]
                        + [s for s in n_drafted if s in sched.running])
        for slot in participants:
            req = sched.running.get(slot)
            if req is None:
                continue
            if slot in plan:
                n = plan[slot]
                req.context_len += n
                if not ok[slot]:
                    # the prompt chunk produced non-finite logits —
                    # quarantine before it ever joins the decode
                    # batch (and before any registration)
                    self._finish_abnormal(req, "nonfinite", events)
                    continue
                if req.prefilling:
                    continue  # mid-prompt: more chunks owed
                # FINAL chunk: commit the prompt's full pages to
                # the prefix index now (first-writer-wins in the
                # pool; the trailing partial page keeps filling
                # during decode and is registered at release)
                seq = req.prompt + req.tokens[:-1]
                self.pool.register_prefix(seq[:req.prefill_target],
                                          req.pages,
                                          include_partial=False,
                                          namespace=req.adapter_ns)
                if self.kv_quant:
                    qs = self._qscale_max(req.pages)
                    self.metrics.on_kv_quant_scale(qs)
                    tr.instant("kv_quantize", track=req.rid,
                               scale_max=round(qs, 6), suffix=n)
                if req.tokens:
                    continue  # recompute after preemption: cache
                              # rebuilt, the stored last token is
                              # the next decode input
                if req.handoff:
                    # disaggregated serving: publish the finished
                    # KV instead of emitting — the decode replica
                    # recomputes this same final row and emits the
                    # bitwise-identical first token itself
                    self._handoff_finish(req, events)
                    continue
                # a chunk slot's last live row is column 0 of samp
                self._emit(req, int(samp[slot, 0]), events)
            else:
                n_draft = n_drafted[slot]
                req.draft_tokens = []
                C0 = req.context_len
                if not ok[slot]:
                    # poison quarantine, same as the decode path:
                    # only this slot finishes (rows are per-slot
                    # independent)
                    req.context_len += 1
                    self._finish_abnormal(req, "nonfinite", events)
                    continue
                m = int(acc[slot])
                if n_draft:
                    self.metrics.on_spec_verify(n_draft, m)
                    self._drafter.observe(req, n_draft, m)
                # the emitted tokens are the engine's own samples
                # for rows 0..m — exactly what m + 1 sequential
                # decode steps would have drawn. A stop (eos)
                # inside the accept window truncates the emission.
                emit: list[int] = []
                for j in range(m + 1):
                    t = int(samp[slot, j])
                    emit.append(t)
                    if ((req.eos_token_id is not None
                         and t == req.eos_token_id)
                            or len(req.tokens) + len(emit)
                            >= req.max_new_tokens):
                        break
                req.context_len = C0 + len(emit)
                if len(emit) < m + 1:
                    # accepted-but-unused tail beyond an in-window
                    # stop: rewind those positions to zero before
                    # the pages can be released/registered (token-
                    # granular masked-garbage-is-zero)
                    self.pool.rewind(req.pages, C0 + len(emit),
                                     C0 + m + 1)
                if tr.enabled and n_draft > m:
                    tr.instant("rollback", track=req.rid,
                               rejected=n_draft - m, accepted=m)
                    tr.bump("spec_rejected_tokens", n_draft - m)
                for t in emit:
                    self._emit(req, t, events)

    def _call_step(self, step, lanes) -> list:
        """Run one step program over ``lanes``: the only place that
        calls one. Every body donates the page arrays (and, for a model
        with recurrent state, the state): the program writes this step's
        rows into the arrays it was given, which are deleted when the
        call returns. What it returns of the pool replaces the pool's
        arrays; the rest is handed back."""
        pool = self.pool
        if not self._hybrid:
            *out, pool.pools = step(self._state, pool.pools, *lanes)
            return out
        *out, pool.pools, pool.state, self._step_counts = step(
            self._state, pool.pools, pool.state, *lanes)
        return out

    def _bump_hybrid_counters(self) -> None:
        """A traced step of a model that takes a ``HybridCache``: the
        slots and bytes of recurrent state it held, or the tokens and
        bytes of latent rows its running requests hold, and what the
        program itself counted in its expert layers (summed over them):
        assignments routed (live rows x top_k), those that landed on a
        held expert, and held experts with at least one row."""
        tr, pool = self.tracer, self.pool
        if self._recurrent:
            live = len(pool.state_slots)
            tr.bump("state_slots_live", live)
            tr.bump("state_bytes_live", live * pool.state_bytes_per_slot)
        if self._latent:
            tokens = sum(r.context_len
                         for r in self.scheduler.running.values())
            tr.bump("latent_tokens_live", tokens)
            tr.bump("latent_bytes_live", tokens * pool.kv_bytes_per_token())
        routed, held, touched = (int(v) for v in
                                 np.asarray(self._step_counts))
        tr.bump("expert_rows_routed", routed)
        tr.bump("expert_rows_held", held)
        tr.bump("experts_touched", touched)

    def _note_retraces(self) -> None:
        """Retrace sentinel, one per step shape ("decode", "mixed"):
        the no-retrace contract says every entry of
        ``step_program_counts()`` stays at 1; any growth lands a
        compile bar + counter bump in the trace right where the
        regression happened."""
        tr = self.tracer
        for name, n in self.step_program_counts().items():
            seen = self._step_traces.get(name, 0)
            if n != seen:
                # the count is followed whether or not the tracer is on,
                # so that a tracer that comes on later (a profiler
                # session) reports only what compiled while it was on
                self._step_traces[name] = n
                tr.instant("compile", program=name, programs=n)
                tr.bump("compiles", n - seen)
                if seen:
                    tr.bump("decode_retraces", n - seen)

    def _watched_sync(self, *arrays):
        """The engine's blocking device sync (np.asarray) under the
        watchdog — a hung device shows up here, so this is where the
        watchdog looks (and where the flight recorder's post-mortem
        hook dumps the event ring before any kill action fires)."""
        from ..distributed.watchdog import default_watchdog
        wd = self._watchdog if self._watchdog is not None \
            else default_watchdog()
        if self.flight_recorder is not None and id(wd) not in self._wd_hooked:
            # one hook per watchdog instance
            self._wd_hooked.add(id(wd))
            recorder = self.flight_recorder

            def _post_mortem(task_rec, _fr=recorder):
                _fr.dump("watchdog_timeout", snapshot={
                    "task": task_rec.name,
                    "meta": {k: repr(v) for k, v in task_rec.meta.items()}})

            wd.post_mortem_hooks.append(_post_mortem)
        tr = self.tracer
        with contextlib.ExitStack() as watched:
            # arming the watchdog (it starts a monitor thread) is host
            # work of its own, not part of the wait for the device
            with tr.span("watchdog_arm"):
                watched.enter_context(wd.task(
                    "serving.step", timeout=self.step_timeout_s,
                    step=self._steps, slots=len(self.scheduler.running)))
            with tr.span("device_sync"):
                return tuple(np.asarray(a) for a in arrays)

    # ------------------------------------------------------------------
    # speculative decoding (serving/speculative.py)
    # ------------------------------------------------------------------

    def _propose_drafts(self) -> None:
        """Host-side draft proposal for every decoding slot (a slot
        still mid-prefill neither decodes nor drafts). The draft count
        is capped so the mixed step can never write beyond the
        request's admission-checked page/position budget: at most k-1
        rows, at most what the remaining token budget could accept
        (m + 1 emits <= remaining), and never past the slot's page
        table or the rope table."""
        spec, drafter = self._spec, self._drafter
        max_pos = min(self.max_pages_per_slot * self.page_size,
                      self.model.config.max_position_embeddings)
        with self.tracer.span("draft",
                              slots=len(self.scheduler.running)):
            for req in self.scheduler.running.values():
                if req.prefilling or not req.tokens:
                    req.draft_tokens = []
                    continue
                cap = min(spec.k - 1,
                          req.max_new_tokens - len(req.tokens) - 1,
                          max_pos - req.context_len - 1)
                drafts = drafter.propose(req, cap) if cap > 0 else []
                req.draft_tokens = [int(t) for t in drafts[:cap]]
                self.metrics.on_spec_draft(len(req.draft_tokens))

    def _emit(self, req: Request, token: int, events: list[dict]) -> None:
        req.tokens.append(token)
        self.metrics.on_token(req.rid)
        self.tracer.bump("tokens")
        reason = None
        if req.eos_token_id is not None and token == req.eos_token_id:
            reason = "stop"
        elif len(req.tokens) >= req.max_new_tokens:
            reason = "length"
        if reason is not None:
            self.scheduler.finish(req, self.pool, reason)
            self.metrics.on_finish(req.rid, reason)
            self._trace_finish(req, reason)
            if self.snapshot_store is not None:
                # terminal: the store is bounded by LIVE requests
                self.snapshot_store.drop(req.rid)
        events.append({"rid": req.rid, "token": token,
                       "finished": reason is not None,
                       "finish_reason": reason})


def _mixed_tail(logits, pools, toks, tables, seq_lens, active, n_live,
                forced, temps, top_ps, greedy, seeds, counts, R, ps,
                stacked):
    """Everything the mixed program does after the logits ``[S, K, V]``:
    the finite sentinel, the samples ``[S, R]``, the accepted count and
    the in-program rollback. ``R`` is the engine's ``spec_k``;
    ``stacked`` addresses the pipeline pool's ``[L, pages, ...]``
    layout."""
    S, K, V = logits.shape
    rows = jnp.arange(K)
    live = rows[None, :] < n_live[:, None]                    # [S, K]
    # per-slot poison sentinel over the LIVE rows only (padded rows read
    # scratch and may be anything)
    ok = jnp.all(jnp.where(live[..., None],
                           jnp.isfinite(logits.astype(jnp.float32)),
                           True), axis=(1, 2))
    with jax.named_scope("sampler"):
        # the host can emit, per slot, only a chunk's last live row (on
        # its final chunk) or a verify slot's rows 0..m, m <= R - 1
        # (drafts are capped at spec_k - 1): gather those R rows, the
        # chunk's in column 0, and sample them alone. Each keeps its
        # ORIGINAL row index in its key; logits stay in the model dtype
        # so argmax/softmax see the same bits the 1-token decode step
        # would
        sel = jnp.clip(jnp.where(forced, n_live - 1, 0)[:, None]
                       + jnp.arange(R)[None, :], 0, K - 1)    # [S, R]
        picked = jnp.take_along_axis(logits, sel[:, :, None], axis=1)
        samp = _sample_rows(
            picked.reshape(S * R, V),
            jnp.repeat(temps, R), jnp.repeat(top_ps, R),
            jnp.repeat(greedy, R), jnp.repeat(seeds, R),
            (counts[:, None] + sel).reshape(-1),
        ).reshape(S, R)
    # accepted count m: a forced (chunk) slot accepts all its rows — its
    # tokens are the prompt, not guesses; a verify slot accepts the
    # longest prefix of live draft rows matching the previous row's
    # sample (rows at or beyond R were never live drafts)
    match = (toks[:, 1:R] == samp[:, :R - 1]) & live[:, 1:R]
    m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    m = jnp.where(forced, n_live - 1, m)                      # [S]
    # in-program rollback: zero the rejected live rows at their real
    # (page, offset); all other rows target scratch (0, 0).
    # Speculatively-written pages are always private to their request
    # (shared full pages are immutable, COW copies partials), so the
    # zeroing can never hit foreign KV. A forced slot has no rejected
    # rows (rows > n_live - 1 are not live), so chunk writes always
    # survive.
    with jax.named_scope("rollback"):
        pos = seq_lens[:, None] + rows[None, :]               # [S, K]
        rej = live & (rows[None, :] > m[:, None]) & active[:, None]
        page = jnp.take_along_axis(tables, pos // ps, axis=1)
        page = jnp.where(rej, page, 0)
        off = jnp.where(rej, pos % ps, 0)
        pools = [tuple(KVCachePool._pos_zero(a, page, off, stacked)
                       for a in pair) for pair in pools]
    return samp, m, ok, pools


def _sample_rows(logits, temps, top_ps, greedy, seeds, counts):
    """Per-slot next-token choice: greedy argmax or nucleus sampling with
    a per-request key stream fold_in(PRNGKey(seed), token_index) —
    independent of slot placement and batch composition, so recompute
    after preemption reproduces the original draws."""
    from ..ops.random import top_p_sampling

    def row(lg, t, p, g, seed, cnt):
        gd = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), cnt)
        probs = jax.nn.softmax(lg.astype(jnp.float32) / t, axis=-1)
        _, idx = top_p_sampling(probs[None], p[None], key=key)
        return jnp.where(g, gd, idx[0, 0].astype(jnp.int32))

    return jax.vmap(row)(logits, temps, top_ps, greedy, seeds, counts)
