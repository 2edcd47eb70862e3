"""Typed failure surface of the serving engine.

Every way a request or the engine can fail maps to exactly one of these
(or to a terminal ``finish_reason`` on the request — see the "Serving
failure modes" table in SERVING.md). Nothing in ``paddle_tpu.serving``
fails with a bare RuntimeError or, worse, a silent busy loop: callers
can catch :class:`ServingError` and know they have seen every
engine-originated failure.

Every subclass carries a machine-readable ``retryable`` class attribute:
``True`` means the same request, submitted unchanged to a *different*
replica (or to the same engine later), can succeed — exactly the
decision a router front-end has to make per error. The in-process
router that acts on it is :class:`paddle_tpu.serving.fleet.FleetRouter`
(SERVING.md "Engine fleet & failover").

- :class:`QueueFullError` — backpressure: ``add_request`` refused
  because the bounded waiting queue is at ``max_queue_depth``.
  ``retryable``: the request is fine, this replica is busy — the fleet
  router retries it on a less-loaded replica (or sheds fleet-wide with
  :class:`FleetOverloadedError` when every replica is saturated).
- :class:`RequestTooLargeError` — the request could NEVER run: its
  prompt + decode budget needs more KV pages than the pool (or a slot)
  has. Rejected at add time — previously such a request silently spun
  in ``admit()`` forever. NOT retryable: every homogeneous replica
  would reject it identically.
- :class:`SchedulerStalledError` — the engine detected a zero-progress
  step (nothing admitted, nothing decoded, work still pending) and
  refuses to busy-loop. Carries a ``snapshot`` dict of the queue/pool
  state for the post-mortem. ``retryable`` — but only on ANOTHER
  replica: this engine's state cannot change on its own, so the fleet
  router ejects the replica and replays its in-flight requests
  elsewhere (deterministic replay, SERVING.md).
- :class:`EngineDrainingError` — ``add_request`` after ``drain()``
  began: the engine is shutting down; the fleet router routes around a
  draining replica automatically.
- :class:`FleetOverloadedError` — fleet-wide load shedding: the
  router's global queue is at capacity, meaning EVERY replica is
  saturated *and* the shared backlog is full. Retryable after backoff
  (clients should retry with jitter), but there is no other replica to
  try — this is the signal to scale out. Carries ``retry_after_s``, the
  router's drain-rate estimate of when capacity frees (RESILIENCE.md
  "Overload playbook").
- :class:`AdmissionShedError` — SLO-aware overload control
  (SERVING.md "Overload control & tenant fairness"): ``add_request``
  shed the request at admission because a per-tenant quota (live slots
  or queued tokens) is exhausted, or because its deadline is
  INFEASIBLE — the estimated queue wait + prefill + decode already
  exceeds the remaining ``deadline_s``, so running it would burn pool
  pages on a guaranteed timeout. Retryable after ``retry_after_s``
  (the engine's deterministic drain-rate estimate); ``kind`` says
  which gate fired (``tenant_quota`` / ``deadline_infeasible``).
- :class:`TPConfigError` — the model cannot be tensor-parallel-sharded
  at the requested degree (``kv_heads % tp``, ``vocab % tp``, … fail)
  or the mesh cannot be built (too few devices). Raised at
  ``ServingEngine(tp=N)`` construction instead of a shape crash inside
  the compiled step. NOT retryable: every replica of the same config
  would fail identically.
- :class:`RecurrentStateError` — a feature that assumes a request's
  whole state is its pages (speculation, the host tier, snapshots and
  hand-off, LoRA, int8 KV) was asked of an engine whose model keeps
  per-slot recurrent state (SERVING.md "Models with recurrent state").
  NOT retryable.
- :class:`LatentCacheError` — a feature that has not been carried over
  to a latent page format (int8 pages, the host tier, snapshots and
  hand-off, speculation, LoRA, a sharded pool) was asked of a pool or
  an engine whose model keeps a latent cache (SERVING.md "Models with a
  latent cache"). NOT retryable.
- :class:`PackedRowsError` — a mixed dispatch would carry more live
  rows than the width its program packs them into (SERVING.md "Packed
  rows"): the width follows ``prefill_token_budget`` as it was when the
  engine was built, so only a budget raised since can trip it. Raised
  on the host before the dispatch; no row is ever dropped. NOT
  retryable: the engine's own configuration is at fault.
- :class:`TransportError` — a fleet wire message failed its blake2b
  digest re-verify at receive (``serving/transport.py``): the payload
  was corrupted in flight. The message is dropped and counted, never
  consumed; retryable — the sender's at-least-once retransmission
  delivers an intact copy.
- :class:`StaleEpochError` — epoch fencing (SERVING.md "Fleet
  transport & membership"): a message carried a replica epoch below
  the receiver's fence, i.e. a zombie replica returning from a
  partition tried to ack work the router already failed over. The
  message is discarded and counted; retryable only in the sense that
  the CURRENT epoch owns the request — the stale sender must never
  retry it.
- :class:`ReplicaSpawnError` — multi-host spawn/attach (SERVING.md
  "Multi-host serving"): a replica host process exited before
  connecting, or the fleet's connect barrier timed out. The fleet was
  never fully formed — nothing to fail over, nothing was accepted.
  Retryable: spawn again (a crashed child usually means a bad spec or
  an environment problem, which the carried exit status pinpoints).
"""

from __future__ import annotations

__all__ = ["ServingError", "QueueFullError", "RequestTooLargeError",
           "SchedulerStalledError", "EngineDrainingError",
           "FleetOverloadedError", "TPConfigError", "AdmissionShedError",
           "RecurrentStateError", "LatentCacheError", "PackedRowsError",
           "TransportError", "StaleEpochError", "ReplicaSpawnError"]


class ServingError(RuntimeError):
    """Base of every typed serving failure.

    ``retryable`` (class attribute, machine-readable): whether the SAME
    request can succeed if resubmitted — to another replica for
    engine-scoped failures, or after backoff for load shedding. The
    conservative base default is False; each subclass states its own.
    """

    retryable: bool = False


class QueueFullError(ServingError):
    """Bounded-queue backpressure: the waiting queue is at capacity.
    Retryable on another replica — ``fleet.FleetRouter`` does exactly
    that (least-loaded placement) instead of bouncing the client."""

    retryable = True


class RequestTooLargeError(ServingError, ValueError):
    """The request can never fit (prompt+decode pages exceed the pool
    or the per-slot table) — rejected at ``add`` instead of spinning.
    Not retryable: homogeneous replicas all reject it identically."""

    retryable = False


class SchedulerStalledError(ServingError):
    """A zero-progress engine step: work is pending but nothing can be
    admitted or decoded, and the state cannot change on its own.
    ``snapshot`` holds the queue/pool evidence. Retryable — on ANOTHER
    replica: the fleet router ejects the stalled engine and replays its
    in-flight requests deterministically elsewhere."""

    retryable = True  # on another replica, never on this one

    def __init__(self, msg: str, snapshot: dict | None = None):
        super().__init__(msg)
        self.snapshot = dict(snapshot or {})


class EngineDrainingError(ServingError):
    """``add_request`` called after ``drain()``: admission is closed.
    Retryable on another replica — the fleet router skips draining
    replicas at placement time."""

    retryable = True


class TPConfigError(ServingError, ValueError):
    """The model/mesh cannot support ``tp=N``: a sharded dimension
    (kv heads, attention heads, vocab, FFN width) is not divisible by
    the TP degree, or fewer than N devices are visible. Raised at
    engine construction — the compiled step never sees the bad shapes.
    Not retryable: homogeneous replicas all reject it identically."""

    retryable = False


class RecurrentStateError(ServingError, ValueError):
    """A feature was asked for that the engine cannot honour for a model
    with per-slot recurrent state (a state-space layer): speculation,
    the host tier, snapshots / restore / hand-off, LoRA, int8 KV. Each
    assumes that a request's whole state is its pages and can be cut at
    any token; a recurrent state can be kept only where it was
    checkpointed. Raised at construction or at the call that asks.

    Not retryable: homogeneous replicas all refuse identically."""

    retryable = False


class LatentCacheError(ServingError, ValueError):
    """A feature was asked for that has not been carried over to a
    latent page format (one ``[pages, page_size, width]`` array a layer,
    a model with latent attention): int8 pages, the host tier,
    snapshots / restore / hand-off, speculation, LoRA, a sharded or
    stacked pool. Each reads or writes a page as a K and a V array of
    heads. Raised at construction or at the call that asks.

    Not retryable: homogeneous replicas all refuse identically."""

    retryable = False


class PackedRowsError(ServingError, ValueError):
    """A mixed dispatch's live rows outnumber the rows its program packs
    them into: ``prefill_token_budget`` was raised after the engine was
    built. Raised before the dispatch, never a row silently dropped.

    Not retryable: the engine's configuration is at fault."""

    retryable = False


class FleetOverloadedError(ServingError):
    """Fleet-wide load shedding (``fleet.FleetRouter.submit``): the
    router's global bounded queue is full, i.e. every healthy replica
    is saturated and the shared backlog on top of them is too. The
    request was not accepted anywhere. Retryable after client-side
    backoff; sustained occurrence means the fleet needs more replicas,
    not more retries. ``retry_after_s`` is the router's deterministic
    drain-rate estimate of when queue capacity frees — clients back
    off at least that long (plus jitter) before resubmitting."""

    retryable = True

    def __init__(self, msg: str, retry_after_s: float | None = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class AdmissionShedError(ServingError):
    """SLO-aware admission shed (``ServingEngine.add_request``): a
    per-tenant quota (live slots / queued tokens) is exhausted, or the
    request's deadline is infeasible given the current backlog — the
    estimated queue wait + prefill + decode time already exceeds
    ``deadline_s``, so admitting it would spend pool pages on a
    guaranteed timeout. Shed BEFORE any resources are held. Retryable
    after ``retry_after_s`` (the engine's drain-rate estimate, 0.0
    when no timing data exists yet); ``kind`` is ``"tenant_quota"`` or
    ``"deadline_infeasible"`` for client-side classification."""

    retryable = True

    def __init__(self, msg: str, retry_after_s: float = 0.0,
                 kind: str = "tenant_quota", tenant: int = 0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s
        self.kind = kind
        self.tenant = tenant


class TransportError(ServingError):
    """A fleet wire message failed its blake2b digest re-verify at
    receive (``serving/transport.py``): corrupted in flight. Dropped
    and counted (``corrupt_dropped``), never consumed. Retryable: the
    sender's at-least-once retransmission delivers an intact copy."""

    retryable = True


class StaleEpochError(ServingError):
    """Epoch fencing: the message's replica epoch is below the
    receiver's fence — a zombie replica back from a partition trying to
    ack work the router already failed over, or a fenced replica being
    handed zombie-epoch commands. Discarded and counted
    (``stale_epoch_discarded`` / ``fenced_dropped``); the CURRENT
    epoch owns the request."""

    retryable = True


class ReplicaSpawnError(ServingError):
    """Multi-host spawn/attach failed (``serving/replica_host.py`` /
    ``SocketTransport.wait_peers``): a replica host process died before
    saying HELLO, or the connect barrier timed out. Raised before any
    request is accepted — the fleet never formed, so there is no
    failover to attempt. Retryable: fix the spec/environment (the
    message carries the child's exit status) and spawn again."""

    retryable = True
