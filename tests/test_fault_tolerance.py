"""Fault-tolerant runtime suite (RESILIENCE.md): atomic verified
checkpoints (commit protocol + SHA-256 shard verification), committed-only
resume discovery, deterministic fault injection (distributed/fault.py),
watchdog abort with post-mortem, preemption drain, and the chaos e2e:
SIGKILL a rank mid-step during an async save and require a bit-identical
resumed loss trajectory."""

import json
import os
import pickle
import signal
import subprocess
import sys
import textwrap
import time

import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(11)


def _cpu_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_FAULT_PLAN", None)
    env.update(extra)
    return env


# --------------------------------------------------------------------------
# commit protocol + verification
# --------------------------------------------------------------------------

def test_save_commits_atomically(tmp_path):
    from paddle_tpu.distributed.checkpoint import (COMMIT_MARKER,
                                                   is_committed,
                                                   save_state_dict)
    path = str(tmp_path / "ck")
    w = jnp.asarray(RNG.standard_normal((4, 3)), jnp.float32)
    save_state_dict({"w": w}, path)
    assert is_committed(path)
    assert os.path.isfile(os.path.join(path, COMMIT_MARKER))
    assert os.path.isfile(os.path.join(path, "metadata.pkl"))
    # staging dir is renamed away, not left behind
    assert not os.path.exists(path + ".tmp")
    # overwriting a committed checkpoint re-commits and leaves no .old swap
    save_state_dict({"w": w * 2}, path)
    assert is_committed(path) and not os.path.exists(path + ".old")
    # checksums landed in the merged metadata
    with open(os.path.join(path, "metadata.pkl"), "rb") as f:
        meta = pickle.load(f)
    assert meta.checksums and all(len(d) == 64
                                  for d in meta.checksums.values())


def test_uncommitted_dir_is_rejected(tmp_path):
    from paddle_tpu.distributed.checkpoint import (CheckpointCorruptionError,
                                                   is_committed,
                                                   load_state_dict)
    torn = tmp_path / "step_5"
    torn.mkdir()
    (torn / "0.distcp.npz").write_bytes(b"partial")
    assert not is_committed(str(torn))
    with pytest.raises(CheckpointCorruptionError, match="never committed"):
        load_state_dict({"w": jnp.zeros((2,))}, str(torn))
    # a *.tmp staging dir is never committed even with a COMMIT inside
    stage = tmp_path / "step_6.tmp"
    stage.mkdir()
    (stage / "COMMIT").write_text("")
    assert not is_committed(str(stage))


def test_flipped_byte_fails_load_naming_shard(tmp_path):
    from paddle_tpu.distributed.checkpoint import (CheckpointCorruptionError,
                                                   save_state_dict,
                                                   load_state_dict)
    path = str(tmp_path / "ck")
    w = jnp.asarray(RNG.standard_normal((16, 8)), jnp.float32)
    save_state_dict({"w": w}, path)
    npz = os.path.join(path, "0.distcp.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(npz, "wb").write(bytes(blob))
    with pytest.raises(CheckpointCorruptionError, match=r"w\|0,0"):
        load_state_dict({"w": jnp.zeros((16, 8))}, path)


def test_tampered_checksum_detected(tmp_path):
    """Exercise the sha256-compare branch itself: the shard file is intact
    (zip CRC passes) but the recorded digest disagrees."""
    from paddle_tpu.distributed.checkpoint import (CheckpointCorruptionError,
                                                   save_state_dict,
                                                   load_state_dict)
    path = str(tmp_path / "ck")
    save_state_dict({"w": jnp.ones((4, 4))}, path)
    meta_path = os.path.join(path, "metadata.pkl")
    with open(meta_path, "rb") as f:
        meta = pickle.load(f)
    (pk,) = meta.checksums
    meta.checksums[pk] = "0" * 64
    with open(meta_path, "wb") as f:
        pickle.dump(meta, f)
    with pytest.raises(CheckpointCorruptionError, match="checksum"):
        load_state_dict({"w": jnp.zeros((4, 4))}, path)


def test_injected_torn_write_is_caught_on_load(tmp_path):
    """Arm the harness's own `torn` action on the shard write and require
    the verification layer to catch the damage."""
    from paddle_tpu.distributed import fault
    from paddle_tpu.distributed.checkpoint import (CheckpointCorruptionError,
                                                   save_state_dict,
                                                   load_state_dict,
                                                   is_committed)
    path = str(tmp_path / "ck")
    fault.activate(fault.FaultPlan([
        {"site": "ckpt.write_shard", "action": "torn"}]))
    try:
        save_state_dict({"w": jnp.asarray(RNG.standard_normal((32, 32)),
                                          jnp.float32)}, path)
    finally:
        fault.deactivate()
    # the save itself succeeded (commit happened) — only verification can
    # tell the shard bytes were torn after hashing
    assert is_committed(path)
    with pytest.raises(CheckpointCorruptionError):
        load_state_dict({"w": jnp.zeros((32, 32))}, path)


# --------------------------------------------------------------------------
# committed-only resume discovery
# --------------------------------------------------------------------------

def test_latest_checkpoint_edge_cases(tmp_path):
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    em = ElasticManager(checkpoint_dir=str(tmp_path))
    assert em.latest_checkpoint() is None  # empty dir
    assert ElasticManager(
        checkpoint_dir=str(tmp_path / "nope")).latest_checkpoint() is None

    # digit-bearing junk must never win: loss traces, notes, torn staging,
    # uncommitted dirs
    (tmp_path / "loss_e12345.txt").write_text("0 1.0\n")
    (tmp_path / "notes_v2").mkdir()
    (tmp_path / "step_99").mkdir()            # uncommitted: no COMMIT/meta
    torn = tmp_path / "step_50.tmp"
    torn.mkdir()
    (torn / "0.distcp.npz").write_bytes(b"x")
    assert em.latest_checkpoint() is None

    (tmp_path / "step_3").mkdir()
    (tmp_path / "step_3" / "COMMIT").write_text("")
    assert em.latest_checkpoint().endswith("step_3")
    # pre-protocol checkpoint (metadata.pkl only) still counts
    (tmp_path / "step_25").mkdir()
    (tmp_path / "step_25" / "metadata.pkl").write_bytes(b"\x80\x04N.")
    assert em.latest_checkpoint().endswith("step_25")

    # gc_torn removes staging leftovers and nothing else
    got = em.latest_checkpoint(gc_torn=True)
    assert got.endswith("step_25")
    assert not torn.exists()
    assert (tmp_path / "step_99").exists()


# --------------------------------------------------------------------------
# FaultPlan semantics
# --------------------------------------------------------------------------

def test_fault_plan_matching_and_once():
    from paddle_tpu.distributed.fault import FaultInjected, FaultPlan
    plan = FaultPlan([{"site": "train.step", "action": "raise",
                       "rank": 1, "step": 3}])
    plan.trip("train.step", rank=0, step=3)   # wrong rank
    plan.trip("train.step", rank=1, step=2)   # wrong step
    plan.trip("other.site", rank=1, step=3)   # wrong site
    with pytest.raises(FaultInjected):
        plan.trip("train.step", rank=1, step=3)
    plan.trip("train.step", rank=1, step=3)   # once=True: spent


def test_fault_plan_nth_and_match():
    from paddle_tpu.distributed.fault import FaultInjected, FaultPlan
    plan = FaultPlan([{"site": "ckpt.commit", "action": "raise", "nth": 3}])
    plan.trip("ckpt.commit", rank=0)
    plan.trip("ckpt.commit", rank=0)
    with pytest.raises(FaultInjected):
        plan.trip("ckpt.commit", rank=0)
    plan2 = FaultPlan([{"site": "ckpt.commit", "action": "raise",
                        "match": r"step_3$"}])
    plan2.trip("ckpt.commit", rank=0, path="/ck/step_30")
    with pytest.raises(FaultInjected):
        plan2.trip("ckpt.commit", rank=0, path="/ck/step_3")


def test_fault_plan_env_roundtrip_and_epoch_gate(monkeypatch):
    from paddle_tpu.distributed import fault
    plan = fault.FaultPlan([{"site": "s", "action": "raise", "epoch": 0}],
                           seed=7)
    again = fault.FaultPlan.from_json(plan.to_json())
    assert again.seed == 7 and again.specs[0].epoch == 0
    monkeypatch.setenv("PADDLE_RESTART_EPOCH", "1")
    again.trip("s", rank=0)  # epoch-gated: silent on the restarted life
    monkeypatch.setenv("PADDLE_RESTART_EPOCH", "0")
    with pytest.raises(fault.FaultInjected):
        again.trip("s", rank=0)


def test_fault_plan_prob_draw_is_deterministic():
    from paddle_tpu.distributed.fault import FaultPlan, FaultSpec
    spec = FaultSpec(site="s", action="raise", prob=0.5, once=False)
    a, b = FaultPlan([spec], seed=3), FaultPlan([spec], seed=3)
    draws_a = [a._draw(spec, r, s) for r in range(4) for s in range(16)]
    draws_b = [b._draw(spec, r, s) for r in range(4) for s in range(16)]
    assert draws_a == draws_b
    assert 0 < sum(draws_a) < len(draws_a)  # actually probabilistic


# --------------------------------------------------------------------------
# watchdog abort: exit code 17 + on-disk post-mortem
# --------------------------------------------------------------------------

def test_watchdog_kill_exits_17_with_diagnosis(tmp_path):
    from paddle_tpu.distributed.watchdog import EXIT_WATCHDOG_ABORT
    script = tmp_path / "hang.py"
    script.write_text(textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {REPO!r})
        import jax; jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.distributed.watchdog import CommWatchdog
        wd = CommWatchdog(timeout=0.3, action="kill",
                          diagnosis_dir={str(tmp_path)!r})
        with wd.task("stuck_allreduce", group="tp", shape=(4096,)):
            time.sleep(60)
    """))
    proc = subprocess.run([sys.executable, str(script)],
                          env=_cpu_env(PADDLE_TRAINER_ID="3"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == EXIT_WATCHDOG_ABORT, (proc.stdout, proc.stderr)
    dump = tmp_path / "watchdog_diagnosis.rank3.json"
    assert dump.exists()
    diag = json.loads(dump.read_text())
    assert diag["rank"] == 3
    (hung,) = [t for t in diag["tasks"] if t["timed_out"]]
    assert hung["name"] == "stuck_allreduce" and not hung["finished"]


# --------------------------------------------------------------------------
# preemption: SIGTERM → drain async save → final checkpoint → exit 143
# --------------------------------------------------------------------------

def test_preemption_guard_drains_and_checkpoints(tmp_path):
    from paddle_tpu.distributed.fleet.preempt import EXIT_PREEMPTED
    ready = tmp_path / "ready"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        import jax; jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        from paddle_tpu.distributed import PreemptionGuard
        from paddle_tpu.distributed.checkpoint import save_state_dict
        guard = PreemptionGuard()
        state = {{"w": jnp.arange(8.0)}}
        # an in-flight async save the guard must drain before the final one
        save_state_dict(state, os.path.join({str(tmp_path)!r}, "step_4"),
                        async_save=True)
        open({str(ready)!r}, "w").write("ok")
        for _ in range(1200):
            time.sleep(0.05)
            guard.check(save_fn=lambda: save_state_dict(
                state, os.path.join({str(tmp_path)!r}, "final")))
        sys.exit(9)  # never preempted
    """))
    proc = subprocess.Popen([sys.executable, str(script)], env=_cpu_env(),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    deadline = time.monotonic() + 90
    while not ready.exists():
        assert time.monotonic() < deadline, proc.communicate(timeout=5)
        assert proc.poll() is None, proc.communicate(timeout=5)
        time.sleep(0.05)
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=90)
    assert proc.returncode == EXIT_PREEMPTED, (out, err)
    from paddle_tpu.distributed.checkpoint import is_committed
    assert is_committed(str(tmp_path / "step_4"))   # drained, not torn
    assert is_committed(str(tmp_path / "final"))    # final sync checkpoint


# --------------------------------------------------------------------------
# chaos e2e: SIGKILL mid-step during an async save; resume bit-identical
# --------------------------------------------------------------------------

_CHAOS_WORKER = """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import jax; jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    epoch = int(os.environ.get("PADDLE_RESTART_EPOCH", "0"))
    ckpt_dir = os.environ["CHAOS_CKPT_DIR"]
    log_dir = os.environ["CHAOS_LOG_DIR"]

    pt.seed(0)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((32, 16)).astype("float32")
    Y = (X @ rng.standard_normal((16, 1)).astype("float32")).ravel()
    model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 1))
    opt = pt.optimizer.SGD(learning_rate=0.05, parameters=model)
    step = pt.jit.TrainStep(model, opt,
                            lambda out, y: ((out.ravel() - y) ** 2).mean(),
                            n_inputs=1)
    em = ElasticManager(checkpoint_dir=ckpt_dir)
    start = 0
    latest = em.latest_checkpoint(gc_torn=(rank == 0))
    if latest:
        model.set_state_dict(load_state_dict(dict(model.state_dict()),
                                             latest))
        start = int(latest.rsplit("_", 1)[1]) + 1
        with open(os.path.join(log_dir, f"resume_e{{epoch}}.r{{rank}}"),
                  "w") as f:
            f.write(os.path.basename(latest))
    step._host_step = start  # RNG/lr streams continue from the true step
    # The order the test's last assertion depends on, made explicit: rank
    # 1's epoch-0 life (nothing to resume from, all 8 losses logged) is
    # over before rank 0 commits a checkpoint, let alone dies at step 4
    # and takes the gang down. Left to the scheduler, a late rank 1
    # resumed from step_0 or was terminated by the launcher mid-run.
    rank1_done = os.path.join(log_dir, "done_e0.r1")
    if (rank, epoch) == (0, 0) and os.environ.get("PADDLE_TRAINERS_NUM") == "2":
        deadline = time.monotonic() + 240
        while not os.path.exists(rank1_done):
            assert time.monotonic() < deadline, "rank 1 never finished"
            time.sleep(0.05)
    handles = {{}}
    for i in range(start, 8):
        if i - 2 in handles:  # commit horizon: step i-2 must be durable
            handles.pop(i - 2).result(timeout=120)
        loss = float(step(X, Y))
        with open(os.path.join(log_dir,
                               f"loss_e{{epoch}}.r{{rank}}.txt"), "a") as f:
            f.write(f"{{i}} {{loss!r}}\\n")
        if rank == 0:
            handles[i] = save_state_dict(
                dict(model.state_dict()),
                os.path.join(ckpt_dir, f"step_{{i}}"),
                async_save=True, async_timeout=120)
    for h in handles.values():
        h.result(timeout=120)
    if (rank, epoch) == (1, 0):
        open(rank1_done, "w").close()
"""


def _read_losses(path):
    return {int(a): float(b) for a, b in
            (ln.split() for ln in path.read_text().splitlines())}


def test_chaos_sigkill_mid_async_save_resumes_bit_identical(tmp_path):
    """The capstone: at epoch 0 rank 0's commit of step_3 hangs (torn
    staging guaranteed) and the next train step SIGKILLs the rank. The
    launcher must classify the death, gang-restart, and the restarted gang
    must resume from step_2 — the newest COMMITTED checkpoint — with every
    recomputed loss bit-identical to a run that never saw a fault."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent(_CHAOS_WORKER.format(repo=REPO)))

    # --- reference: same worker, no launcher, no faults
    ref_ckpt, ref_log = tmp_path / "ref_ck", tmp_path / "ref_log"
    ref_ckpt.mkdir(), ref_log.mkdir()
    proc = subprocess.run(
        [sys.executable, str(script)],
        env=_cpu_env(CHAOS_CKPT_DIR=str(ref_ckpt), CHAOS_LOG_DIR=str(ref_log),
                     PADDLE_TRAINER_ID="0", PADDLE_RESTART_EPOCH="0"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    ref = _read_losses(ref_log / "loss_e0.r0.txt")
    assert sorted(ref) == list(range(8))

    # --- faulted gang: hang step_3's commit, SIGKILL rank 0 at step 4
    ckpt, log = tmp_path / "ck", tmp_path / "log"
    ckpt.mkdir(), log.mkdir()
    plan = {"seed": 0, "specs": [
        {"site": "ckpt.commit", "action": "hang", "arg": 120.0,
         "rank": 0, "epoch": 0, "match": r"step_3$"},
        {"site": "train.step", "action": "kill",
         "rank": 0, "step": 4, "epoch": 0},
    ]}
    code = textwrap.dedent(f"""
        import sys; sys.path.insert(0, {REPO!r})
        from paddle_tpu.distributed.launch.main import launch
        sys.exit(launch(["--nproc_per_node", "2", "--max_restarts", "2",
                         {str(script)!r}]))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=_cpu_env(CHAOS_CKPT_DIR=str(ckpt), CHAOS_LOG_DIR=str(log),
                     PADDLE_FAULT_PLAN=json.dumps(plan)),
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "killed-by-SIGKILL" in proc.stderr     # exit classification
    assert "gang restart 1/2" in proc.stderr

    # resumed from the newest COMMITTED checkpoint: step_3 was torn
    assert (log / "resume_e1.r0").read_text() == "step_2"
    # the torn staging dir was GC'd on the restart path
    assert not (ckpt / "step_3.tmp").exists()

    e0 = _read_losses(log / "loss_e0.r0.txt")
    e1 = _read_losses(log / "loss_e1.r0.txt")
    assert sorted(e0) == [0, 1, 2, 3]      # killed inside step 4
    assert sorted(e1) == [3, 4, 5, 6, 7]   # resumed after step_2
    # bit-identical: overlap step AND the whole union against the
    # unfaulted reference (repr round-trips float64 exactly)
    assert e1[3] == e0[3]
    merged = {**e0, **e1}
    assert merged == ref, (merged, ref)
    # every surviving checkpoint is committed; rank 1's epoch-0 life also
    # ran to completion writing its own trajectory
    from paddle_tpu.distributed.checkpoint import is_committed
    for i in range(3, 8):
        assert is_committed(str(ckpt / f"step_{i}"))
    assert _read_losses(log / "loss_e0.r1.txt") == ref
