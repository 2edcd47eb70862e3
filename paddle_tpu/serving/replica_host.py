"""Process-isolated fleet replicas: ``python -m
paddle_tpu.serving.replica_host`` (SERVING.md "Multi-host serving").

One replica host = one OS process owning one real
:class:`~.engine.ServingEngine` behind an :class:`~.transport.EngineServer`,
speaking the canonical PR-15 wire to the router over a
:class:`~.transport_socket.SocketTransport`. The process builds its
model from a JSON spec (same seed + same config = bitwise-identical
weights in every replica — the determinism contract crosses the
process boundary with no weight shipping), warms the step programs
BEFORE dialing the router (compilation happens outside any lease), and
then runs the host loop::

    pump the socket -> run at most one latched engine step -> repeat

The :class:`~.transport.EngineServer` runs in deferred step mode, so a
burst of retransmitted STEPs can never wedge the process in
back-to-back engine steps and starve its heartbeat acks into a lease
expiry.

Kill semantics (the whole point):

- SIGTERM — the existing preemption guard trips; the host runs the
  engine's drain and streams an unsolicited ``DRAIN_RESULTS``
  (``EngineServer.announce_drain``) so in-flight requests finish or
  classify as ``preempted``, flushes its socket, and exits 143
  (``EXIT_PREEMPTED``).
- SIGKILL — nothing graceful CAN happen, which is the scenario the
  fleet is built for: the router notices pure silence (lease expiry),
  fences the epoch, and replays the dead replica's requests elsewhere
  — snapshot-seeded when a fetched snapshot exists. The router-side
  handle classifies the corpse post-mortem (``signal:SIGKILL``).

The parent-side API is :func:`spawn_fleet` — spawn N hosts on
localhost, wait for their HELLOs, and return a ready
``FleetRouter(transport=SocketTransport(...))`` driving them purely
through the wire — plus :class:`RemoteEngineHandle` (the engine-shaped
stand-in the router holds: pid/addr/post-mortem, no serving-path
calls) and :func:`reap_orphans` (test hygiene: no replica process may
outlive its test).

Spec keys (all optional): ``seed`` (weight seed, default 0),
``config`` (llama_tiny config overrides), ``engine`` (ServingEngine
kwargs, e.g. num_pages/page_size/max_slots/snapshot_interval),
``snapshots`` (bool: give the engine a PRIVATE in-process
SnapshotStore — the router harvests it over the wire via
SNAPSHOT_FETCH, modelling per-host stores that die with the host
unless fetched).

Children inherit ``JAX_PLATFORMS`` (forced to ``cpu`` when unset) and
single-thread BLAS caps from :func:`spawn_fleet`, so a test fleet
stays inside the CI budget.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

__all__ = ["RemoteEngineHandle", "spawn_fleet", "shutdown_fleet",
           "reap_orphans", "build_engine", "serve"]

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# every process this module ever spawned (until reaped) — the test
# fixture sweeps it so no replica can outlive its test
_SPAWNED: list = []


# ---------------------------------------------------------------------------
# parent side: handles + spawn/attach
# ---------------------------------------------------------------------------


class RemoteEngineHandle:
    """The engine-shaped object a router holds for an out-of-process
    replica. ``is_remote`` makes the router skip building a local
    EngineServer (the real one lives in the child, bound to the same
    ``replica:i`` name on the far end of the socket); everything else
    the router touches out-of-band (``pool``, ``snapshot_store``,
    ``flight_recorder``) reads None. What the handle CAN do is classify
    the process's fate — ``post_mortem()`` feeds the router's ejection
    bookkeeping and ``health()``'s ``exit_status``."""

    is_remote = True
    snapshot_store = None
    flight_recorder = None
    pool = None

    def __init__(self, idx: int, proc, addr: str | None = None):
        self.idx = int(idx)
        self.proc = proc
        self.addr = addr            # "ip:port" once connected

    @property
    def pid(self) -> int:
        return self.proc.pid

    def poll(self):
        return self.proc.poll()

    def post_mortem(self) -> str:
        """Classify how the process died: ``signal:SIGKILL`` (and
        friends) for signal deaths, ``preempted:SIGTERM`` for a clean
        guard-drained 143, ``exit:N`` otherwise, ``running`` if it has
        not died at all (a lease can expire on a live-but-wedged
        process — that distinction matters in a post-mortem)."""
        rc = self.proc.poll()
        if rc is None:
            return "running"
        if rc < 0:
            try:
                return f"signal:{signal.Signals(-rc).name}"
            except ValueError:
                return f"signal:{-rc}"
        from ..distributed.fleet.preempt import EXIT_PREEMPTED
        if rc == EXIT_PREEMPTED:
            return "preempted:SIGTERM"
        return f"exit:{rc}"

    def kill(self) -> None:
        self.proc.kill()

    def terminate(self) -> None:
        self.proc.terminate()

    def wait(self, timeout: float | None = None):
        return self.proc.wait(timeout)


def spawn_fleet(n: int, spec: dict | None = None,
                host: str = "127.0.0.1", *,
                router_kwargs: dict | None = None,
                transport_kwargs: dict | None = None,
                spawn_timeout_s: float = 120.0):
    """Spawn ``n`` replica host processes on ``host``, wait for every
    HELLO, and return ``(router, handles)`` — a
    ``FleetRouter(transport=SocketTransport(...))`` already attached to
    the live fleet. Raises :class:`~.errors.ReplicaSpawnError` (after
    killing whatever did spawn) if any child dies first or the barrier
    times out.

    The router's membership knobs default to wall-clock-scaled values
    (a router step over sockets is ~``poll_s``, not a synchronous
    loopback call): lease ~600 steps, heartbeats every 2, drain/shed
    patience in the thousands. Override via ``router_kwargs``."""
    from .fleet import FleetRouter
    from .snapshot import SnapshotStore
    from .transport_socket import SocketTransport

    spec = dict(spec or {})
    tkw = dict(transport_kwargs or {})
    transport = SocketTransport("router", listen=(host, 0), **tkw)
    addr = transport.listen_addr
    # the children inherit JAX_PLATFORMS as it is (tests set cpu in
    # conftest; unset, each child takes JAX's own default device): the
    # environment decides the platform, never a fallback in here
    env = dict(os.environ)
    for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                "MKL_NUM_THREADS"):
        env.setdefault(var, "1")
    env["PYTHONPATH"] = _REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs, handles = [], []
    try:
        for i in range(n):
            proc = subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.serving.replica_host",
                 "--router", f"{addr[0]}:{addr[1]}", "--idx", str(i),
                 "--spec-json", json.dumps(spec)],
                env=env, cwd=_REPO_ROOT)
            _SPAWNED.append(proc)
            procs.append(proc)
            handles.append(RemoteEngineHandle(i, proc))
        transport.wait_peers([f"replica:{i}" for i in range(n)],
                             timeout_s=spawn_timeout_s, procs=procs)
    except BaseException:
        for p in procs:
            if p.poll() is None:
                p.kill()
        transport.close()
        raise
    for h in handles:
        h.addr = transport.peer_addr(f"replica:{h.idx}")
    rkw = dict(router_kwargs or {})
    rkw.setdefault("lease_steps", 600)
    rkw.setdefault("heartbeat_interval", 2)
    rkw.setdefault("shed_patience", 5000)
    rkw.setdefault("drain_patience", 3000)
    rkw.setdefault("snapshot_fetch_interval", 8)
    if spec.get("snapshots") and "snapshot_store" not in rkw:
        # the router-side durable medium the per-host private stores
        # are harvested into — what survives a SIGKILL
        rkw["snapshot_store"] = SnapshotStore()
    router = FleetRouter(handles, transport=transport, **rkw)
    return router, handles


def shutdown_fleet(router, handles, timeout_s: float = 10.0) -> None:
    """Graceful teardown: SIGTERM every live child (its guard drains
    and exits 143), escalate to SIGKILL past ``timeout_s``, close the
    router's transport."""
    for h in handles:
        if h.poll() is None:
            try:
                h.terminate()
            except OSError:
                pass
    deadline = time.monotonic() + float(timeout_s)
    for h in handles:
        if h.poll() is None:
            try:
                h.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                h.kill()
                h.wait(5.0)
    transport = getattr(router, "transport", None)
    if transport is not None and hasattr(transport, "close"):
        transport.close()


def reap_orphans() -> int:
    """SIGKILL every process this module spawned that is still alive,
    and forget them all. Returns how many needed killing — a conftest
    fixture asserts this is 0 after a well-behaved test."""
    killed = 0
    for proc in _SPAWNED:
        if proc.poll() is None:
            killed += 1
            try:
                proc.kill()
                proc.wait(10.0)
            except OSError:
                pass
    _SPAWNED.clear()
    return killed


# ---------------------------------------------------------------------------
# child side: the host process
# ---------------------------------------------------------------------------


def build_engine(spec: dict):
    """Construct the replica's engine from the spec — deterministically:
    ``pt.seed(spec['seed'])`` before init means every replica of the
    same spec holds bitwise-identical weights without any weight
    transfer."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    from .engine import ServingEngine
    from .snapshot import SnapshotStore

    pt.seed(int(spec.get("seed", 0)))
    cfg_kw = dict(spec.get("config") or {})
    cfg_kw.setdefault("mp_axis", None)
    cfg_kw.setdefault("fsdp_axis", None)
    model = LlamaForCausalLM(llama_tiny(**cfg_kw))
    model.eval()
    eng_kw = dict(spec.get("engine") or {})
    eng_kw.setdefault("num_pages", 64)
    eng_kw.setdefault("page_size", 4)
    eng_kw.setdefault("max_slots", 4)
    if spec.get("snapshots"):
        eng_kw.setdefault("snapshot_store", SnapshotStore())
    return ServingEngine(model, **eng_kw)


def serve(idx: int, router_addr: tuple, spec: dict, *,
          drain_timeout_s: float | None = 5.0,
          idle_exit_s: float = 120.0,
          poll_s: float = 0.002) -> int:
    """The host loop. Returns the process exit code (143 after a
    SIGTERM drain, 0 on router-gone idle exit)."""
    from ..distributed.fleet.preempt import EXIT_PREEMPTED
    from .transport import EngineServer
    from .transport_socket import SocketTransport

    engine = build_engine(spec)
    # SIGTERM -> the EXISTING drain guard, armed before the (slow)
    # warm so a preemption during compile still exits cleanly
    guard = engine.attach_preemption_guard()
    engine.warm_programs()      # compile OUTSIDE any lease window
    # warm the advisory read paths too: the first pool.utilization() /
    # audit_pool() call jit-compiles, which would otherwise eat the
    # router's first (timeout-bounded) gauges/introspect query
    pool = getattr(engine, "pool", None)
    if pool is not None:
        pool.utilization()
    audit = getattr(engine, "audit_pool", None)
    if audit is not None:
        audit()
    transport = SocketTransport(
        f"replica:{idx}", connect={"router": router_addr}, poll_s=poll_s)
    server = EngineServer(idx, engine, transport, step_mode="deferred")
    last_routed = time.monotonic()
    step = 0
    try:
        while True:
            step += 1
            transport.tick(step)
            transport.pump()
            if server.pending_step():
                server.run_pending_step()
            if guard.preempted:
                server.announce_drain(timeout_s=drain_timeout_s)
                deadline = time.monotonic() + 5.0
                while (transport.pending_output()
                       and time.monotonic() < deadline):
                    transport.pump()
                return EXIT_PREEMPTED
            if "router" in transport.peers():
                last_routed = time.monotonic()
            elif time.monotonic() - last_routed > idle_exit_s:
                # the router has been gone for a long time: the parent
                # died without killing us — exit instead of orphaning
                return 0
    finally:
        transport.close()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="paddle_tpu fleet replica host process")
    parser.add_argument("--router", required=True,
                        help="router host:port to dial")
    parser.add_argument("--idx", type=int, required=True,
                        help="replica index (names this endpoint)")
    parser.add_argument("--spec-json", default="{}",
                        help="engine/model spec as a JSON object")
    parser.add_argument("--drain-timeout-s", type=float, default=5.0)
    parser.add_argument("--idle-exit-s", type=float, default=120.0)
    args = parser.parse_args(argv)

    host, _, port = args.router.rpartition(":")
    spec = json.loads(args.spec_json)
    return serve(args.idx, (host, int(port)), spec,
                 drain_timeout_s=args.drain_timeout_s,
                 idle_exit_s=args.idle_exit_s)


if __name__ == "__main__":
    sys.exit(main())
