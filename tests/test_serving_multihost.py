"""Multi-host serving: SocketTransport + process-isolated replicas.

Contracts under test (SERVING.md "Multi-host serving"):

1. FRAMING — the length-prefixed wire format round-trips a Message
   (digests verbatim, snapshots included); damaged frames raise typed
   :class:`FrameProtocolError`, damaged BODIES survive framing and die
   at the existing digest gate — never a wrong byte delivered.
2. SOCKET FLEET PARITY — a FleetRouter driving EngineServers over real
   TCP loopback produces the exact streams the in-process loopback
   fleet pins, exactly-once, including across an abrupt connection
   death (lease expiry -> epoch fence -> replay: no NEW failover
   logic, the PR-15 machinery fires from socket-shaped silence).
3. FRAME CHAOS — byte corruption, mid-frame RSTs and stalls at the
   connection layer degrade to the same counters/fallbacks the
   message-level ChaosTransport pins (corrupt_injected ==
   corrupt_dropped; resets -> torn frames + reconnects; stalls ->
   half-open teardown), with streams bitwise intact.
4. FAULT SITES — ``fleet.transport.connect`` / ``fleet.transport.accept``
   make connection ESTABLISHMENT itself lossy, deterministically.
5. REAL PROCESSES (slow tier) — ``spawn_fleet`` children are genuine
   OS processes: SIGKILL one mid-stream and every client stream stays
   bitwise identical to a single-engine ``generate()`` run,
   exactly-once, snapshot-seeded when a fetched snapshot exists;
   SIGTERM drains via the preemption guard and exits 143.

Fast tier runs on scripted fake engines over real localhost TCP
(tier-1); the subprocess sweeps ride ``slow``/``faults`` markers.
Every test in this module carries a hard SIGALRM timeout — a wedged
socket loop fails loudly instead of eating the suite's budget.
"""

import os
import signal
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.distributed import fault
from paddle_tpu.observability import parse_prometheus, render_fleet_prometheus
from paddle_tpu.serving import (FleetRouter, FrameChaos, FrameDecoder,
                                LoopbackTransport, Message, SocketTransport)
from paddle_tpu.serving import replica_host
from paddle_tpu.serving.fleet import DEAD
from paddle_tpu.serving.replica_host import (RemoteEngineHandle, shutdown_fleet,
                                             spawn_fleet)
from paddle_tpu.serving.transport import EngineServer
from paddle_tpu.serving.transport_socket import (FT_HELLO, FT_MESSAGE,
                                                 FrameProtocolError, _frame,
                                                 decode_message,
                                                 encode_message)

from test_serving_transport import (FakeEngine, _collect_tokens, _expected,
                                    _submit_payload)

_FAST_TIMEOUT_S = 60
_SLOW_TIMEOUT_S = 300


@pytest.fixture(autouse=True)
def _hard_timeout(request):
    """Per-test wall-clock ceiling (CI hygiene): socket loops that wedge
    must fail THIS test, not stall the whole run."""
    budget = (_SLOW_TIMEOUT_S if request.node.get_closest_marker("slow")
              else _FAST_TIMEOUT_S)

    def _expired(signum, frame):
        raise TimeoutError(
            f"{request.node.nodeid} exceeded its {budget}s hard timeout")

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(budget)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture
def fault_free(monkeypatch):
    fault.deactivate()
    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    monkeypatch.delenv("PADDLE_RESTART_EPOCH", raising=False)
    monkeypatch.delenv("PADDLE_FAULT_PLAN", raising=False)
    yield
    fault.deactivate()


# ---------------------------------------------------------------------------
# framing: FrameDecoder + Message codec
# ---------------------------------------------------------------------------

class TestFrameDecoder:
    def test_byte_by_byte_reassembly(self):
        blob = (_frame(FT_HELLO, b"replica:0")
                + _frame(FT_MESSAGE, b"x" * 300))
        dec = FrameDecoder()
        frames = []
        for i in range(len(blob)):
            frames.extend(dec.feed(blob[i:i + 1]))
        assert frames == [(FT_HELLO, b"replica:0"),
                          (FT_MESSAGE, b"x" * 300)]
        assert dec.pending == 0

    def test_coalesced_and_split_arbitrarily(self):
        msgs = [_frame(FT_MESSAGE, bytes([i]) * i) for i in range(1, 6)]
        blob = b"".join(msgs)
        for cut in (1, 3, 7, len(blob)):
            dec = FrameDecoder()
            out = []
            for off in range(0, len(blob), cut):
                out.extend(dec.feed(blob[off:off + cut]))
            assert [p for _, p in out] == [bytes([i]) * i
                                           for i in range(1, 6)]

    def test_torn_frame_is_pending_not_delivered(self):
        f = _frame(FT_MESSAGE, b"abcdef")
        dec = FrameDecoder()
        assert dec.feed(f[:-2]) == []
        assert dec.pending > 0          # counted as torn on teardown

    def test_bad_magic_raises_typed(self):
        dec = FrameDecoder()
        with pytest.raises(FrameProtocolError):
            dec.feed(b"XY" + b"\x01\x00\x00\x00\x00")

    def test_unknown_frame_type_raises(self):
        dec = FrameDecoder()
        with pytest.raises(FrameProtocolError):
            dec.feed(_frame(FT_MESSAGE, b"")[:2] + b"\x7f\x00\x00\x00\x00")

    def test_oversize_length_raises_before_buffering(self):
        import struct
        hdr = struct.pack(">2sBI", b"PT", FT_MESSAGE, (1 << 30) + 1)
        with pytest.raises(FrameProtocolError):
            FrameDecoder().feed(hdr)


class TestMessageWire:
    def test_round_trip_verbatim(self):
        m = Message.make("SUBMIT", "router", "replica:1", epoch=3, seq=17,
                         rid="r9", payload=_submit_payload("r9", [5], 4))
        d = decode_message(encode_message(m))
        assert (d.kind, d.src, d.dst, d.epoch, d.seq, d.rid) \
            == (m.kind, m.src, m.dst, m.epoch, m.seq, m.rid)
        assert d.body == m.body and d.digest == m.digest
        assert d.verify() and d.payload() == m.payload()

    def test_snapshot_blobs_cross_bitwise(self):
        from paddle_tpu.serving.snapshot import RequestSnapshot
        snap = RequestSnapshot(
            rid="r1", prompt=[1, 2, 3], max_new_tokens=8,
            eos_token_id=None, temperature=1.0, top_p=1.0,
            do_sample=False, seed=0, arrival_seq=0, tokens=[7, 8],
            context_len=4, step=4, kv_tag="kv", page_size=4,
            payloads=[[np.arange(8, dtype=np.float32).reshape(4, 2)],
                      [np.ones((4, 2), np.float32)]]).seal()
        m = Message.make("KV_OFFER", "replica:0", "router", rid="r1",
                         payload={"rid": "r1"}, snaps=(snap,))
        d = decode_message(encode_message(m))
        assert len(d.snaps) == 1
        got = d.snaps[0]
        assert got.verify()             # digests traveled verbatim
        assert got.tokens == snap.tokens
        for a, b in zip(got.payloads[0] + got.payloads[1],
                        snap.payloads[0] + snap.payloads[1]):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    def test_flipped_body_byte_fails_digest_not_framing(self):
        m = Message.make("STEP", "router", "replica:0",
                         payload={"router_step": 1, "ack": 0})
        wire = bytearray(encode_message(m))
        wire[-1] ^= 0xFF                # last body byte
        d = decode_message(bytes(wire))  # framing still parses...
        assert not d.verify()            # ...the digest gate catches it

    def test_garbage_payload_raises_typed(self):
        with pytest.raises(FrameProtocolError):
            decode_message(b"\x00\x00\x00\xffgarbage")
        with pytest.raises(FrameProtocolError):
            decode_message(b"\x00")


# ---------------------------------------------------------------------------
# in-process fleets over real localhost TCP (scripted engines)
# ---------------------------------------------------------------------------

class _FakeProc:
    """Just enough of subprocess.Popen for RemoteEngineHandle: the
    in-process 'replica process' whose fate the test scripts."""

    def __init__(self, pid=4242, returncode=None):
        self.pid = pid
        self.returncode = returncode

    def poll(self):
        return self.returncode


class _SocketFleet:
    """A FleetRouter over real TCP with in-process scripted replicas:
    each replica is a FakeEngine behind an EngineServer bound to its
    own SocketTransport dialing the router's listener."""

    def __init__(self, n=2, *, router_tr_kw=None, rep_tr_kw=None,
                 router_kw=None):
        tr_kw = dict(poll_s=0.0005, query_timeout_s=0.05)
        tr_kw.update(router_tr_kw or {})
        self.rt = SocketTransport("router", listen=("127.0.0.1", 0),
                                  **tr_kw)
        self.reps = []
        for i in range(n):
            rkw = dict(poll_s=0.0005)
            rkw.update(rep_tr_kw or {})
            tr = SocketTransport(f"replica:{i}",
                                 connect={"router": self.rt.listen_addr},
                                 **rkw)
            eng = FakeEngine()
            srv = EngineServer(i, eng, tr)
            self.reps.append((tr, eng, srv))
        self.dead = set()
        want = {f"replica:{i}" for i in range(n)}
        deadline = time.monotonic() + 15
        while set(self.rt.peers()) != want:
            self.pump_replicas()
            self.rt.pump()
            assert time.monotonic() < deadline, "socket fleet never formed"
        self.handles = [RemoteEngineHandle(i, _FakeProc(pid=4000 + i))
                        for i in range(n)]
        kw = dict(lease_steps=60)
        kw.update(router_kw or {})
        self.router = FleetRouter(self.handles, transport=self.rt, **kw)

    def pump_replicas(self):
        for i, (tr, _, _) in enumerate(self.reps):
            if i not in self.dead:
                tr.pump()

    def kill(self, idx, rc=-9):
        """SIGKILL semantics, in-process: the replica's sockets vanish
        and it goes silent forever."""
        self.dead.add(idx)
        self.reps[idx][0].close()
        self.handles[idx].proc.returncode = rc

    def drive(self, *, until_emitted=None, max_steps=20000):
        events, steps = [], 0
        while self.router.has_work():
            events.extend(self.router.step())
            self.pump_replicas()
            steps += 1
            assert steps < max_steps, "socket fleet hang"
            if until_emitted is not None:
                emitted = sum(len(r.tokens)
                              for r in self.router._records.values())
                if emitted >= until_emitted:
                    break
        return events

    def close(self):
        for i, (tr, _, _) in enumerate(self.reps):
            if i not in self.dead:
                tr.close()
        self.rt.close()

    def assert_exact(self, rids, events, prompts, max_new):
        seen = _collect_tokens(events)
        for rid, p in zip(rids, prompts):
            rec = self.router.request(rid)
            assert rec.finished and rec.finish_reason == "length", rid
            assert rec.tokens == _expected(list(p), max_new), rid
            assert seen.get(rid, []) == rec.tokens       # exactly-once


class TestSocketFleet:
    def test_parity_with_loopback_fleet(self, fault_free):
        prompts, max_new = [[p] for p in (2, 3, 5, 7, 9)], 6
        fleet = _SocketFleet(2)
        try:
            rids = [fleet.router.submit(list(p), max_new) for p in prompts]
            events = fleet.drive()
            fleet.assert_exact(rids, events, prompts, max_new)
            st = fleet.rt.stats()
            assert st["socket_frames_sent"] > 0
            assert st["socket_bytes_recv"] > 0
            assert fleet.rt.counters["corrupt_dropped"] == 0
            # socket latency expires no lease on a healthy wire
            assert fleet.router.fleet_metrics.counters[
                "lease_expirations"] == 0
            # same streams the default loopback fleet produces
            router = FleetRouter([FakeEngine(), FakeEngine()])
            lrids = [router.submit(list(p), max_new) for p in prompts]
            while router.has_work():
                router.step()
            for rid, lrid in zip(rids, lrids):
                assert (fleet.router.request(rid).tokens
                        == router.request(lrid).tokens)
        finally:
            fleet.close()

    def test_abrupt_connection_death_fails_over_exactly_once(
            self, fault_free):
        prompts, max_new = [[p] for p in (2, 3, 5, 7, 9, 11)], 8
        fleet = _SocketFleet(2, router_kw=dict(lease_steps=20))
        try:
            rids = [fleet.router.submit(list(p), max_new) for p in prompts]
            events = fleet.drive(until_emitted=6)
            # kill a replica that actually HOSTS a live request
            victim = next(fleet.router.request(r).replica for r in rids
                          if fleet.router.request(r).replica is not None
                          and not fleet.router.request(r).finished)
            fleet.kill(victim, rc=-signal.SIGKILL)
            events += fleet.drive()
            fleet.assert_exact(rids, events, prompts, max_new)
            h = fleet.router.health(victim)
            assert h["state"] == DEAD
            assert h["exit_status"] == "signal:SIGKILL"
            assert h["pid"] == 4000 + victim
            fm = fleet.router.fleet_metrics.counters
            assert fm["lease_expirations"] >= 1
            assert fm["failovers"] >= 1
            # the corpse's queued frames became honest drops, never
            # wrong bytes
            assert fleet.rt.counters["corrupt_dropped"] == 0
        finally:
            fleet.close()

    def test_health_and_prometheus_carry_pid_addr_exit(self, fault_free):
        fleet = _SocketFleet(2)
        try:
            rid = fleet.router.submit([3], 4)
            fleet.drive()
            assert fleet.router.request(rid).tokens == _expected([3], 4)
            for i in range(2):
                h = fleet.router.health(i)
                assert h["pid"] == 4000 + i
                assert h["addr"] == fleet.rt.peer_addr(f"replica:{i}")
                assert h["exit_status"] is None
            page = render_fleet_prometheus(fleet.router)
            series = parse_prometheus(page)      # strict: every line
            assert series['paddle_serving_fleet_replica_pid'
                          '{replica="0"}'] == 4000
            assert any(k.startswith("paddle_serving_fleet_replica_info")
                       for k in series)
            assert series["paddle_serving_fleet_transport_"
                          "socket_frames_sent_total"] > 0
        finally:
            fleet.close()

    def test_query_round_trips_over_the_wire(self, fault_free):
        fleet = _SocketFleet(1)
        try:
            stop = threading.Event()

            def pump():
                while not stop.is_set():
                    fleet.reps[0][0].pump()

            th = threading.Thread(target=pump, daemon=True)
            th.start()

            def ask(kind):
                # queries are advisory (timeout -> None); retry like the
                # router does, while the pump thread answers
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    out = fleet.rt.query("replica:0", kind, {})
                    if out is not None:
                        return out
                    fleet.rt.pump()
                return None

            try:
                g = ask("gauges")
                ins = ask("introspect")
            finally:
                stop.set()
                th.join()
            assert g is not None and g["pid"] == os.getpid()
            assert ins is not None and ins["pid"] == os.getpid()
            # unknown peer degrades to None, never raises
            assert fleet.rt.query("replica:9", "gauges", {}) is None
        finally:
            fleet.close()


class TestDeferredStepMode:
    def test_step_burst_latches_to_one_engine_step(self, fault_free):
        t = LoopbackTransport()
        t.bind("router")
        eng = FakeEngine()
        srv = EngineServer(0, eng, t, step_mode="deferred")
        t.send(Message.make("SUBMIT", "router", "replica:0", epoch=1,
                            rid="r1", payload=_submit_payload("r1", [3], 4)))
        t.pump()
        for k in range(3):              # a burst of retransmitted STEPs
            t.send(Message.make("STEP", "router", "replica:0", epoch=1,
                                payload={"router_step": k, "ack": 0}))
            t.pump()
        assert eng.steps == 0           # latched, not executed
        assert srv.pending_step()
        srv.run_pending_step()
        assert eng.steps == 1           # the burst collapsed to ONE step
        assert not srv.pending_step()
        t.pump()
        results = [m for m in t.recv("router") if m.kind == "STEP_RESULTS"]
        assert results and results[-1].payload()["events"]

    def test_invalid_mode_rejected(self):
        t = LoopbackTransport()
        with pytest.raises(ValueError):
            EngineServer(0, FakeEngine(), t, step_mode="bogus")


# ---------------------------------------------------------------------------
# frame-layer chaos: corruption / resets / stalls / half-open
# ---------------------------------------------------------------------------

class TestFrameChaos:
    def test_corruption_caught_by_digest_gate_streams_bitwise(
            self, fault_free):
        prompts, max_new = [[p] for p in (2, 3, 5)], 6
        fleet = _SocketFleet(
            2, router_tr_kw=dict(chaos=FrameChaos(seed=7, corrupt_p=0.08)),
            router_kw=dict(lease_steps=400))
        try:
            rids = [fleet.router.submit(list(p), max_new) for p in prompts]
            events = fleet.drive()
            fleet.assert_exact(rids, events, prompts, max_new)
            injected = fleet.rt.counters["corrupt_injected"]
            caught = sum(tr.counters["corrupt_dropped"]
                         for tr, _, _ in fleet.reps)
            assert injected > 0
            assert caught == injected   # every flipped byte was caught
        finally:
            fleet.close()

    def test_mid_frame_resets_torn_then_reconnect_bitwise(
            self, fault_free):
        prompts, max_new = [[p] for p in (2, 3, 5)], 6
        fleet = _SocketFleet(
            2, router_tr_kw=dict(chaos=FrameChaos(seed=3, reset_p=0.03)),
            router_kw=dict(lease_steps=400))
        try:
            rids = [fleet.router.submit(list(p), max_new) for p in prompts]
            events = fleet.drive()
            fleet.assert_exact(rids, events, prompts, max_new)
            assert fleet.rt.counters["socket_resets"] >= 1
            rep_counts = [tr.counters for tr, _, _ in fleet.reps]
            assert sum(c["socket_torn_frames"] for c in rep_counts) >= 1
            assert sum(c["socket_reconnects"] for c in rep_counts) >= 1
            assert all(c["corrupt_dropped"] == 0 for c in rep_counts)
        finally:
            fleet.close()

    def test_backpressure_is_bounded_not_oom(self, fault_free):
        # a stalled link + a tiny outbound budget: the queue saturates,
        # stalls are counted, overflow becomes honest drops
        fleet = _SocketFleet(
            1, router_tr_kw=dict(
                outbound_limit=4,
                chaos=FrameChaos(seed=1, stall_p=1.0, stall_s=30.0)))
        try:
            for i in range(16):
                fleet.rt.send(Message.make(
                    "STEP", "router", "replica:0", epoch=1,
                    payload={"router_step": i, "ack": 0}))
                fleet.rt.pump()
            c = fleet.rt.counters
            assert c["socket_backpressure_stalls"] > 0
            assert c["dropped"] > 0
            assert len(fleet.rt._out["replica:0"]) <= 4
        finally:
            fleet.close()

    def test_half_open_link_detected_and_torn_down(self, fault_free):
        fleet = _SocketFleet(
            1, router_tr_kw=dict(ping_interval_s=0.01, half_open_s=0.05))
        try:
            # the replica goes silent but its socket stays open: only
            # the ping/pong probe can tell this from a healthy idle link
            deadline = time.monotonic() + 10
            while fleet.rt.counters["socket_half_open"] == 0:
                fleet.rt.pump()          # replica NOT pumped: no pongs
                assert time.monotonic() < deadline, "half-open undetected"
            assert "replica:0" not in fleet.rt.peers()
        finally:
            fleet.close()

    def test_send_to_gone_peer_is_honest_loss(self, fault_free):
        fleet = _SocketFleet(1)
        try:
            fleet.kill(0)
            drops0 = fleet.rt.counters["dropped"]
            # router side notices the EOF on its next sweep, then sends
            # land in the no-peer-no-dial branch
            deadline = time.monotonic() + 10
            while "replica:0" in fleet.rt.peers():
                fleet.rt.pump()
                assert time.monotonic() < deadline
            fleet.rt.send(Message.make("FENCE", "router", "replica:0",
                                       epoch=5, payload={"epoch": 5}))
            fleet.rt.pump()
            assert fleet.rt.counters["dropped"] > drops0
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# connection-establishment fault sites
# ---------------------------------------------------------------------------

class TestConnectionFaultSites:
    def test_connect_drop_backs_off_then_connects(self, fault_free):
        plan = fault.activate(fault.FaultPlan([fault.FaultSpec(
            site="fleet.transport.connect", action="drop",
            match="^router$", once=True)]))
        fleet = _SocketFleet(1, rep_tr_kw=dict(reconnect_base_s=0.005))
        try:
            assert len(plan._fired) == 1          # the first dial died
            rid = fleet.router.submit([3], 4)     # ...and nobody noticed
            fleet.drive()
            assert fleet.router.request(rid).tokens == _expected([3], 4)
        finally:
            fleet.close()

    def test_accept_raise_is_an_rst_then_redial(self, fault_free):
        fault.activate(fault.FaultPlan([fault.FaultSpec(
            site="fleet.transport.accept", action="raise", once=True)]))
        fleet = _SocketFleet(1, rep_tr_kw=dict(reconnect_base_s=0.005))
        try:
            # the listener RST the first attempt (counted as a reset on
            # the accept side), the dialer retried, the fleet formed
            assert fleet.rt.counters["socket_resets"] >= 1
            rid = fleet.router.submit([5], 4)
            fleet.drive()
            assert fleet.router.request(rid).tokens == _expected([5], 4)
        finally:
            fleet.close()

    def test_connect_delay_parks_the_dial(self, fault_free):
        fault.activate(fault.FaultPlan([fault.FaultSpec(
            site="fleet.transport.connect", action="delay", arg=0.2,
            match="^router$", once=True)]))
        t0 = time.monotonic()
        fleet = _SocketFleet(1)
        try:
            assert time.monotonic() - t0 >= 0.2   # the dial waited
            assert fleet.rt.peers() == ["replica:0"]
        finally:
            fleet.close()

    def test_plan_replays_from_env(self, fault_free, monkeypatch):
        # PADDLE_FAULT_PLAN is the cross-process arming path replica
        # hosts inherit: the same JSON must round-trip to the same plan
        plan = fault.FaultPlan([fault.FaultSpec(
            site="fleet.transport.connect", action="drop",
            match="^router$", once=True)], seed=5)
        clone = fault.FaultPlan.from_json(plan.to_json())
        assert [s.site for s in clone.specs] == ["fleet.transport.connect"]
        assert clone.specs[0].action == "drop" and clone.seed == 5


# ---------------------------------------------------------------------------
# slow tier: real OS processes (spawn, SIGKILL, SIGTERM)
# ---------------------------------------------------------------------------

def _reference_streams(spec, workload):
    """The single-engine ground truth: same seed, same config, same
    prompts through model.generate — what every fleet stream must match
    bitwise."""
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    pt.seed(int(spec.get("seed", 0)))
    cfg = dict(spec.get("config") or {})
    cfg.setdefault("mp_axis", None)
    cfg.setdefault("fsdp_axis", None)
    model = LlamaForCausalLM(llama_tiny(**cfg))
    model.eval()
    refs = []
    for prompt, max_new in workload:
        out = model.generate(jnp.asarray([prompt]), max_new_tokens=max_new)
        refs.append(np.asarray(out)[0, len(prompt):].tolist())
    return refs


_SPEC = {"seed": 0, "snapshots": True,
         "engine": {"num_pages": 64, "page_size": 4, "max_slots": 4,
                    "snapshot_interval": 2}}
_WORKLOAD = [([1 + i, 7, 3], 12) for i in range(6)]


def _drive_fleet(router, *, stop=None, max_steps=40000):
    steps = 0
    while router.has_work():
        router.step()
        steps += 1
        assert steps < max_steps, "real-process fleet hang"
        if stop is not None and stop():
            break
    return steps


def _emitted(router, rids):
    return sum(len(router.request(r).tokens) for r in rids)


def _introspect(router, idx, tries=5):
    for _ in range(tries):
        out = router.transport.query(f"replica:{idx}", "introspect", {})
        if out is not None:
            return out
    return None


@pytest.mark.slow
@pytest.mark.faults
class TestRealProcessFleet:
    @pytest.mark.parametrize("kill_after", [6, 40])
    def test_sigkill_mid_stream_is_bitwise_exactly_once(self, fault_free,
                                                        kill_after):
        refs = _reference_streams(_SPEC, _WORKLOAD)
        router, handles = spawn_fleet(
            3, _SPEC, router_kwargs={"snapshot_fetch_interval": 2})
        try:
            rids = [router.submit(list(p), m) for p, m in _WORKLOAD]
            _drive_fleet(router,
                         stop=lambda: _emitted(router, rids) >= kill_after)
            victim = router.request(rids[0]).replica
            if victim is None or router.health(victim)["state"] == DEAD:
                victim = 1
            handles[victim].kill()       # real SIGKILL, real process
            handles[victim].wait(10)
            _drive_fleet(router)

            for rid, ref in zip(rids, refs):
                rec = router.request(rid)
                assert rec.finished and rec.finish_reason in ("length",
                                                              "stop")
                assert rec.tokens == ref, (
                    f"{rid}: fleet stream diverged from generate()")
            h = router.health(victim)
            assert h["state"] == DEAD
            assert h["exit_status"] == "signal:SIGKILL"
            assert h["pid"] == handles[victim].pid
            fm = router.fleet_metrics.counters
            assert fm["lease_expirations"] >= 1
            assert fm["failovers"] >= 1
            if kill_after >= 40:
                # killed late: fetched snapshots existed, so recovery
                # was snapshot-seeded — replay strictly shorter than
                # regenerating every token from scratch
                assert fm["snapshot_restores"] >= 1
                assert fm["recovery_restored_tokens"] > 0
            # survivors: pinned program set, clean page accounting
            for idx in range(3):
                if idx == victim:
                    continue
                ins = _introspect(router, idx)
                assert ins is not None, f"replica {idx} unreachable"
                assert ins["audit_ok"], ins.get("audit_error")
                counts = ins["step_program_counts"]
                assert set(counts) <= {"decode", "mixed", "prefill"}
                assert sum(counts.values()) <= 4
        finally:
            shutdown_fleet(router, handles)

    def test_sigterm_drains_and_exits_preempted(self, fault_free):
        refs = _reference_streams(_SPEC, _WORKLOAD[:4])
        router, handles = spawn_fleet(2, _SPEC)
        try:
            rids = [router.submit(list(p), m) for p, m in _WORKLOAD[:4]]
            _drive_fleet(router,
                         stop=lambda: _emitted(router, rids) >= 8)
            handles[0].terminate()       # SIGTERM -> guard -> drain
            rc = handles[0].wait(30)
            assert rc == 143             # EXIT_PREEMPTED
            assert handles[0].post_mortem() == "preempted:SIGTERM"
            _drive_fleet(router)
            for rid, ref in zip(rids, refs):
                rec = router.request(rid)
                assert rec.finished
                assert rec.finish_reason in ("length", "stop", "preempted")
                # NEVER wrong bytes: whatever was delivered is a prefix
                # of the ground-truth stream
                assert rec.tokens == ref[:len(rec.tokens)], rid
        finally:
            shutdown_fleet(router, handles)

    def test_spawn_failure_raises_and_leaves_no_orphans(self, fault_free):
        from paddle_tpu.serving import ReplicaSpawnError
        bad = {"seed": 0, "config": {"vocab_size": -1}}   # child dies
        with pytest.raises(ReplicaSpawnError):
            spawn_fleet(1, bad, spawn_timeout_s=60)
        assert replica_host.reap_orphans() == 0
