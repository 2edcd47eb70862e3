"""Benchmark suite: one JSON line per BASELINE.md measurement config, on one
TPU chip.

Configs (BASELINE.md "measurement configs"):
  - llama_420m  : Llama decoder pretraining, seq 2048, bf16, flash attention
                  (the round-2 headline metric; keep MFU >= 0.507)
  - resnet50    : ImageNet-shape conv training, images/sec
  - bert_base   : MLM+NSP pretraining step, seq 512, DP-shape attention
  - qwen2_moe   : sparse MoE decoder step (grouped-GEMM dispatch, one chip)
  - lenet_mnist : BASELINE config 1, single-device correctness reference
                  (correctness-only metric: did the loss fall; its
                  milliseconds-long steps make img/s too noisy to score)
  - llama8b_shape: 2 Llama-3-8B-config decoder layers + 128k-vocab fused CE,
                  seq 4096 bf16 remat — north-star-shape MFU on one chip
  - llama_decode: serving decode — compiled prefill + one-program lax.scan
                  token loop; steady-state decode tokens/s at batch 1 and 8
  - llama_longctx: the flagship at seq 16384 with remat — long-context;
                  10-step windows (extra.iters) since each step is ~0.8 s
  - llama_longctx_32k (OPT-IN, run by name): same at seq 32768
  - llama_decode_int8 / llama_serving_int8: the quantized-serving arms —
                  int8 KV cache + int8 weight streaming (SERVING.md
                  "Quantized KV & weights"); MBU against *necessary* int8
                  bytes, bytes_ratio_vs_bf16 is the bandwidth headroom

Each line: {"metric", "value", "unit", "vs_baseline", "extra"}. The primary
(first) line is llama_420m — vs_baseline remains MFU/0.40 against the
BASELINE.json north-star target. Other configs report their own MFU-based
vs_baseline against the same 0.40 target (BASELINE.md publishes no absolute
reference numbers — "to measure").

Protocol (round 4): every config is fed THROUGH its input pipeline inside
the timed loop (llama: native pack_sequences over variable-length docs;
others: DataLoader over synthetic datasets) and timed over 3 windows of 30
steps; extra carries {pipeline, runs, spread}. Device batches are
pre-staged and cycled (see _time_windows docstring).

Chip peak FLOP/s and HBM bandwidth come from device_kind through ONE
table each (_PEAKS, _HBM_BW); a device that is not in them is an error,
never a default, and main() refuses to run on anything but a TPU
(``--dry`` excepted): a CPU number is never printed as a device number.

Pass config names as argv to run a subset: `python bench.py llama_420m`.

Driver contract: the LAST stdout line is always one JSON object
``{"bench_summary": {config: {value, mfu, spread}}}`` covering every
selected config (value null for failed ones) — emitted before the
failure SystemExit so a partial run still reports what it measured.
``--dry`` skips all device work (and the jax import) and emits only the
summary skeleton; the CI smoke test asserts the contract against it.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# nominal bf16 dense peak FLOP/s by TPU generation (public spec sheets)
_PEAKS = {
    "v4": 275e12,
    "v5e": 197e12, "v5litepod": 197e12, "v5lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12, "trillium": 918e12,
}


# HBM bandwidth by generation (public spec sheets), for MBU — keyed by
# the SAME aliases as _PEAKS; no default
_HBM_BW = {
    "v4": 1.2e12,
    "v5e": 0.82e12, "v5litepod": 0.82e12, "v5lite": 0.82e12,
    "v5p": 2.77e12,
    "v6e": 1.64e12, "trillium": 1.64e12,
}


def _detect_peak(dev) -> tuple[float, str]:
    """(peak FLOP/s, table key) of a device whose kind is in _PEAKS;
    raises for any other device."""
    kind = getattr(dev, "device_kind", "").lower().replace(" ", "")
    for key, peak in _PEAKS.items():
        if key in kind:
            return peak, key
    raise ValueError(
        f"bench: device kind {dev.device_kind!r} (platform "
        f"{dev.platform!r}) is in neither _PEAKS nor _HBM_BW; add its "
        f"published peaks there — no MFU/MBU is scored against a guess")


_RUNS = 3  # timed windows per config (reported in extra.runs)

# latency SLOs the serving configs score goodput_at_slo against
# (SERVING.md "Tracing & SLOs"): requests/s that finished normally AND
# met both budgets — TTFT from arrival, p99 of the request's own
# inter-token gaps. The prefix config gets the tighter TTFT budget its
# cache exists to deliver.
_SERVING_SLOS = {
    "llama_serving": {"ttft_p99_s": 2.0, "itl_p99_s": 0.25},
    "llama_serving_prefix": {"ttft_p99_s": 1.0, "itl_p99_s": 0.25},
    # int8 arm: same workload and SLOs as llama_serving — quantization
    # must not be allowed to hide behind looser targets
    "llama_serving_int8": {"ttft_p99_s": 2.0, "itl_p99_s": 0.25},
    # fleet arm: a replica is killed mid-run, so failed-over requests
    # pay re-prefill + replay inside one inter-token gap — the looser
    # ITL budget is the failover price the SLO explicitly allows
    "llama_serving_fleet": {"ttft_p99_s": 2.0, "itl_p99_s": 1.0},
    # failover A/B (full vs bounded replay): same kill, same budgets as
    # the fleet arm — snapshots must win on replay work, not on SLOs
    "llama_serving_failover": {"ttft_p99_s": 2.0, "itl_p99_s": 1.0},
    # partition A/B (clean vs lossy wire): retransmissions and a healed
    # partition stretch inter-token gaps — the fleet ITL budget prices
    # the lease ejection + replay, same as any other failover
    "llama_serving_partition": {"ttft_p99_s": 2.0, "itl_p99_s": 1.0},
    # multi-host A/B (loopback vs real localhost TCP): the socket wire
    # adds a per-step frame round-trip to every inter-token gap — the
    # fleet ITL budget prices it, and both arms score against the same
    # targets so the framing overhead shows up in goodput, not excuses
    "llama_serving_multihost": {"ttft_p99_s": 2.0, "itl_p99_s": 1.0},
    # chunked-prefill A/B: long prompts land mid-decode, so the OFF
    # arm's itl_p99 carries the head-of-line stall chunking removes; a
    # tight ITL SLO makes goodput_at_slo sensitive to exactly that
    "llama_serving_chunked": {"ttft_p99_s": 4.0, "itl_p99_s": 0.25},
    # speculative arm: same workload/SLOs as llama_serving — drafting
    # must not be allowed to trade latency SLOs for throughput. itl is
    # per-EMITTED-token, so accepted multi-token steps help, not hurt
    "llama_serving_spec": {"ttft_p99_s": 2.0, "itl_p99_s": 0.25},
    # tiered arm: prefix-cache SLOs — the host tier's job is to keep
    # the hit path (and its TTFT) alive under pool pressure
    "llama_serving_tiered": {"ttft_p99_s": 1.0, "itl_p99_s": 0.25},
    # overload A/B: generous TTFT bound (the trace deliberately floods
    # the queue — what matters is the COLD tenants' p99 against it and
    # the goodput delta between the FCFS and fair+brownout arms)
    "llama_serving_fairness": {"ttft_p99_s": 4.0, "itl_p99_s": 0.5},
    # tensor-parallel A/B: same workload and SLOs as llama_serving —
    # the mesh must not hide behind looser targets; both arms report
    # goodput against the identical budget
    "llama_serving_tp": {"ttft_p99_s": 2.0, "itl_p99_s": 0.25},
    # pp arm: same workload and SLOs as llama_serving_tp — staging the
    # decoder must not be allowed to hide behind looser targets
    "llama_serving_pp": {"ttft_p99_s": 2.0, "itl_p99_s": 0.25},
    # disaggregated prefill/decode A/B: the long-prompt trace makes
    # TTFT prefill-dominated (chunked 10x prompts take seconds on the
    # bench chip), so the TTFT budget is generous — the SLO that the
    # split exists to protect is ITL: decode replicas never run prefill
    # chunks, so inter-token gaps must stay flat as prompts grow
    "llama_serving_disagg": {"ttft_p99_s": 8.0, "itl_p99_s": 1.0},
    # multi-tenant LoRA arm: same workload and SLOs as llama_serving —
    # paging adapters through the slot pool must not hide behind looser
    # targets; the A/B vs the single-adapter arm prices the churn
    "llama_serving_lora": {"ttft_p99_s": 2.0, "itl_p99_s": 0.25},
}


def _time_windows(step_fn, feed, iters=30, runs=_RUNS):
    """Median step time over `runs` timed windows of `iters` steps, the
    input pipeline IN the measured loop: every step calls ``feed()``, which
    performs the host-side pipeline work (DataLoader iteration / sequence
    packing) and returns the device batch for the step (VERDICT r3 missing
    #6 — one repeated in-memory batch hides host-bound regressions).

    Device feeds cycle a small set of PRE-STAGED device batches instead of
    shipping each host batch. Host pipeline cost lands in the window the
    way it does in production: llama's pack_sequences runs serially per step; the
    DataLoader configs pop the buffer-reader thread's queue, so their
    host cost only shows when the pipeline cannot keep up with the
    device step (queue starvation).

    Returns (median_dt, spread, last_loss) with spread = (max-min)/median
    over the window means.
    """
    loss = step_fn(*feed())
    _ = float(np.asarray(loss).ravel()[0])  # compile + warmup
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step_fn(*feed())
        lossv = float(np.asarray(loss).ravel()[0])
        times.append((time.perf_counter() - t0) / iters)
    assert np.isfinite(lossv), lossv
    med = sorted(times)[len(times) // 2]
    spread = (max(times) - min(times)) / med
    return med, spread, lossv


def _staged_feed(host_iter, staged):
    """feed() closure: drive the host pipeline one batch per call, return
    the next staged device batch (see _time_windows on why transfer is
    staged). ``feed.close()`` releases the pipeline (drains an in-flight
    DataLoader epoch so its prefetcher thread exits instead of pinning the
    dataset in memory for the rest of the multi-config bench process)."""
    it = iter(host_iter)
    k = [0]

    def feed():
        next(it)  # host pipeline work, in the timed loop
        k[0] += 1
        return staged[k[0] % len(staged)]

    def close():
        for obj in (host_iter, it):
            if hasattr(obj, "close"):
                obj.close()
                break
    feed.close = close
    return feed


class _LoaderCycle:
    """Endless epochs over a DataLoader. The loader's buffer-reader thread
    has no stop signal — it runs until its epoch drains — so close()
    consumes the in-flight epoch's tail to let the thread exit."""

    def __init__(self, loader):
        self.loader = loader
        self.it = iter(loader)

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self.it)
        except StopIteration:
            self.it = iter(self.loader)
            return next(self.it)

    def close(self):
        for _ in self.it:
            pass


class _SynthImages:
    """Pre-generated image shards served as whole batches (IterableDataset
    protocol): one vectorized fancy-index per batch instead of 128
    per-item copies + stack — per-item collate of 77 MB fp32 batches
    cannot keep up with a ~60 ms device step (the 30-step windows surfaced
    exactly that host-bound starvation), while production image pipelines
    read pre-batched/pre-decoded shards at memcpy speed."""

    def __init__(self, n, batch, batches_per_epoch=64):
        r = np.random.default_rng(1)
        self.x = r.standard_normal((n, 3, 224, 224)).astype(np.float32)
        self.y = r.integers(0, 1000, (n,)).astype(np.int64)
        self.batch = batch
        self.batches_per_epoch = batches_per_epoch
        self._rng = np.random.default_rng(2)

    def __iter__(self):
        for _ in range(self.batches_per_epoch):
            idx = self._rng.integers(0, len(self.y), self.batch)
            yield self.x[idx], self.y[idx]


def _llama_flagship(seq, recompute):
    """Shared flagship construction for the llama configs: returns
    (cfg, model, n_params, step, flops_per_token)."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                      num_hidden_layers=8, num_attention_heads=16,
                      num_key_value_heads=8, max_position_embeddings=seq,
                      dtype="bfloat16", mp_axis=None, fsdp_axis=None,
                      recompute=recompute)
    model = LlamaForCausalLM(cfg)
    n_params = model.num_params()
    opt = pt.optimizer.AdamW(learning_rate=1e-4, parameters=model)
    step = pt.jit.TrainStep(model, opt,
                            lambda logits, labels: model.loss(logits, labels))
    fpt = 6.0 * n_params + 12.0 * cfg.num_hidden_layers * seq * cfg.hidden_size
    return cfg, model, n_params, step, fpt


def bench_llama(peak, peak_kind):
    import jax.numpy as jnp

    batch, seq = 4, 2048  # sweep 2026-07: fastest no-remat point on v5e
    cfg, model, n_params, step, flops_per_token = _llama_flagship(
        seq, recompute=False)
    rng = np.random.default_rng(0)
    # input pipeline: variable-length documents packed into fixed rows via
    # the native packer (io/native_loader.pack_sequences), batch rows per
    # host step
    from paddle_tpu.io.native_loader import pack_sequences
    docs = [rng.integers(0, cfg.vocab_size, rng.integers(128, seq + 1))
            .astype(np.int32) for _ in range(256)]

    def host_batches():
        i = 0
        while True:
            chunk = [docs[(i + j) % len(docs)] for j in range(batch * 2)]
            i += batch * 2
            rows, _ = pack_sequences(chunk, seq)
            for r0 in range(0, len(rows) - batch + 1, batch):
                yield rows[r0:r0 + batch]

    staged = [(a := jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                                jnp.int32), a) for _ in range(4)]
    pipe = _staged_feed(host_batches(), staged)
    try:
        dt, spread, lossv = _time_windows(step, pipe)
    finally:
        pipe.close()
    tokens_per_sec = batch * seq / dt
    mfu = flops_per_token * tokens_per_sec / peak
    return {
        "metric": "llama_420m_seq2048_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu": round(mfu, 4), "step_ms": round(dt * 1000, 2),
                  "params": n_params, "loss": round(lossv, 4),
                  "batch": batch, "seq": seq, "peak": peak_kind,
                  "pipeline": True, "runs": _RUNS, "spread": round(spread, 4)},
    }


def bench_resnet50(peak, peak_kind, batch=128):  # 128 ~20% > 64/256 (sweep)
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    pt.seed(0)
    model = resnet50(num_classes=1000)
    # AMP O2: bf16 conv/fc params + bf16 input, fp32 batch norms, fp32
    # master weights in the optimizer (reference bench: DP+AMP, SURVEY A.2)
    model = pt.amp.decorate(model, level="O2")
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=model)
    step = pt.jit.TrainStep(model, opt,
                            lambda out, y: F.cross_entropy(out, y))
    rng = np.random.default_rng(0)
    # input pipeline: pre-batched image shards through the DataLoader's
    # buffer-reader thread (see _SynthImages) — a host-bound pipeline
    # surfaces as queue starvation in the timed window
    from paddle_tpu.io import DataLoader, IterableDataset

    class _Shards(_SynthImages, IterableDataset):
        pass

    # each dataset item IS a batch: batch_size=1 + unwrap collate
    loader = DataLoader(_Shards(8 * batch, batch), batch_size=1,
                        collate_fn=lambda items: items[0], to_device=False)
    staged = [(jnp.asarray(rng.standard_normal((batch, 3, 224, 224)),
                           jnp.bfloat16),
               jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32))
              for _ in range(2)]
    pipe = _staged_feed(_LoaderCycle(loader), staged)
    try:
        dt, spread, lossv = _time_windows(step, pipe)
    finally:
        pipe.close()
    images_per_sec = batch / dt
    # ResNet-50 @224 is 4.09 GMACs = 8.18 GFLOP forward per image (the
    # widely quoted "4.09 GFLOPs" counts multiply-accumulates; summing the
    # actual conv inventory — tools/profile_resnet_convs.py — gives
    # ~8.5e9/img incl. projections). Round-3 artifacts used 4.09e9 and so
    # UNDERcounted MFU 2x. train ≈ 3x fwd (bwd ~2x).
    mfu = 3 * 8.18e9 * images_per_sec / peak
    # honest chip ceiling (PROFILE_resnet50.md round 5): ~50 ms/step at
    # batch 128 — XLA conv-custom-call core at 46% of peak + BN already
    # below its standalone bandwidth floor. Report how close the step sits
    # so a regression reads as at_ceiling_frac dropping, not as "MFU low".
    ceiling_ms = 50.0 * batch / 128
    return {
        "metric": "resnet50_224_images_per_sec_per_chip",
        "value": round(images_per_sec, 1),
        "unit": "images/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu": round(mfu, 4), "step_ms": round(dt * 1000, 2),
                  "ceiling_step_ms": round(ceiling_ms, 2),
                  "at_ceiling_frac": round(ceiling_ms / (dt * 1000), 4),
                  "loss": round(lossv, 4), "batch": batch, "peak": peak_kind,
                  "pipeline": True, "runs": _RUNS, "spread": round(spread, 4)},
    }


def bench_bert(peak, peak_kind, batch=32):
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models.bert import BertConfig, BertForPreTraining

    pt.seed(0)
    seq = 512
    cfg = BertConfig(dtype="bfloat16", hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    model = BertForPreTraining(cfg)
    n_params = model.num_params() if hasattr(model, "num_params") else int(sum(
        np.prod(v.shape) for v in model.state_dict().values()))
    opt = pt.optimizer.AdamW(learning_rate=1e-4, parameters=model)

    def loss_fn(outputs, labels):
        mlm_logits, nsp_logits = outputs
        mlm_labels, nsp_labels = labels
        return model.loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels)

    step = pt.jit.TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    from paddle_tpu.io import DataLoader, Dataset

    class SynthMLM(Dataset):
        # 16 batches/epoch: epoch restarts respawn the buffer-reader
        # thread; keep that churn out of the 10-step timed windows
        def __init__(self):
            r = np.random.default_rng(1)
            self.ids = r.integers(0, cfg.vocab_size,
                                  (16 * batch, seq)).astype(np.int32)
            self.nsp = r.integers(0, 2, (16 * batch,)).astype(np.int32)

        def __len__(self):
            return 16 * batch

        def __getitem__(self, i):
            return self.ids[i], self.ids[(i + 1) % len(self.ids)], self.nsp[i]

    loader = DataLoader(SynthMLM(), batch_size=batch, shuffle=True,
                        drop_last=True, to_device=False)

    def stage():
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                          jnp.int32)
        mlm = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                          jnp.int32)
        nsp = jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32)
        return (ids, (mlm, nsp))

    staged = [stage() for _ in range(4)]
    pipe = _staged_feed(_LoaderCycle(loader), staged)
    try:
        dt, spread, lossv = _time_windows(step, pipe)
    finally:
        pipe.close()
    tokens_per_sec = batch * seq / dt
    mfu = 6.0 * n_params * tokens_per_sec / peak
    return {
        "metric": "bert_base_seq512_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu": round(mfu, 4), "step_ms": round(dt * 1000, 2),
                  "params": n_params, "loss": round(lossv, 4),
                  "batch": batch, "seq": seq, "peak": peak_kind,
                  "pipeline": True, "runs": _RUNS, "spread": round(spread, 4)},
    }


def bench_qwen2_moe(peak, peak_kind, batch=8,  # sweep r4: 8 > 4/16 (bf16)
                    ep_dispatch="grouped"):
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models.qwen2_moe import Qwen2MoeConfig, Qwen2MoeForCausalLM

    pt.seed(0)
    seq = 1024
    cfg = Qwen2MoeConfig(vocab_size=32000, hidden_size=1024,
                         intermediate_size=2816, moe_intermediate_size=704,
                         shared_expert_intermediate_size=2816,
                         num_hidden_layers=8, num_attention_heads=16,
                         num_key_value_heads=8, num_experts=16,
                         num_experts_per_tok=2, max_position_embeddings=seq,
                         dtype="bfloat16", mp_axis=None, fsdp_axis=None,
                         ep_axis=None, ep_dispatch=ep_dispatch)
    model = Qwen2MoeForCausalLM(cfg)
    n_params = int(sum(np.prod(v.shape)
                       for v in model.state_dict().values()))
    # active params per token: dense stack + shared expert + top-k routed
    cfg2 = cfg
    routed_per_layer = 3 * cfg2.hidden_size * cfg2.moe_intermediate_size
    n_active = n_params - cfg2.num_hidden_layers * (
        cfg2.num_experts - cfg2.num_experts_per_tok) * routed_per_layer
    opt = pt.optimizer.AdamW(learning_rate=1e-4, parameters=model)
    step = pt.jit.TrainStep(model, opt,
                            lambda logits, labels: model.loss(logits, labels))
    rng = np.random.default_rng(0)
    from paddle_tpu.io import DataLoader, Dataset

    class SynthTokens(Dataset):
        # 16 batches/epoch: see SynthMLM note on buffer-reader churn
        def __init__(self):
            r = np.random.default_rng(1)
            self.ids = r.integers(0, cfg.vocab_size,
                                  (16 * batch, seq)).astype(np.int32)

        def __len__(self):
            return 16 * batch

        def __getitem__(self, i):
            return self.ids[i]

    loader = DataLoader(SynthTokens(), batch_size=batch, shuffle=True,
                        drop_last=True, to_device=False)
    staged = [(a := jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                                jnp.int32), a) for _ in range(4)]
    pipe = _staged_feed(_LoaderCycle(loader), staged)
    try:
        dt, spread, lossv = _time_windows(step, pipe)
    finally:
        pipe.close()
    tokens_per_sec = batch * seq / dt
    mfu = 6.0 * n_active * tokens_per_sec / peak
    suffix = "" if ep_dispatch == "grouped" else f"_{ep_dispatch}"
    return {
        "metric": f"qwen2_moe_16e_seq1024_tokens_per_sec_per_chip{suffix}",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu_active": round(mfu, 4), "step_ms": round(dt * 1000, 2),
                  "params_total": n_params, "params_active": int(n_active),
                  "loss": round(lossv, 4), "batch": batch, "seq": seq,
                  "experts": cfg.num_experts, "dispatch": ep_dispatch,
                  "peak": peak_kind,
                  "pipeline": True, "runs": _RUNS, "spread": round(spread, 4)},
    }


def bench_lenet(peak, peak_kind, batch=256):
    """BASELINE config 1: MNIST LeNet — the single-device correctness
    reference. Reports images/s and asserts the loss actually falls over
    the measured windows (the other configs only check finiteness)."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    pt.seed(0)
    model = LeNet(num_classes=10)
    opt = pt.optimizer.Adam(learning_rate=1e-3, parameters=model)
    step = pt.jit.TrainStep(model, opt, lambda o, y: F.cross_entropy(o, y))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 1, 28, 28)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, (batch,)), jnp.int32)
    first = float(np.asarray(step(x, y)).ravel()[0])  # compile + step 0
    # 100-step windows: milliseconds-long steps need more of them per
    # window for a stable mean
    dt, spread, lossv = _time_windows(step, lambda: (x, y), iters=100)
    # no assert: a did-not-train run must still EMIT the value-0.0 line
    # (the driver reads vs_baseline, not a traceback)
    images_per_sec = batch / dt
    # correctness-only metric (VERDICT r4 weak #3): report did-it-train
    # as the value; img/s of milliseconds-long steps stays in extra,
    # labeled as not scored.
    return {
        "metric": "lenet_mnist_correctness",
        "value": 1.0 if lossv < first else 0.0,
        "unit": "loss_fell",
        "vs_baseline": 1.0 if lossv < first else 0.0,
        "extra": {"step_ms": round(dt * 1000, 3), "loss0": round(first, 4),
                  "loss": round(lossv, 4), "batch": batch,
                  "images_per_sec_unreliable": round(images_per_sec, 1),
                  "throughput_note": "host sync jitter is of the order "
                                     "of the step time; img/s not scored",
                  "peak": peak_kind, "pipeline": False, "runs": _RUNS,
                  "spread": round(spread, 4)},
    }


def bench_llama_longctx(peak, peak_kind, batch=1, seq=16384):
    """Long-context (SURVEY §5.7; default at 16k since round 5 — VERDICT r4
    weak #5 wanted the number in the driver artifact): the same Llama
    flagship at long seq on ONE chip — Pallas flash attention (no O(S^2)
    materialization) + per-layer remat. 10-step windows (long steps;
    extra.iters records the deviation from the default 30). seq-32k stays opt-in:
    ``python bench.py llama_longctx_32k``."""
    import jax.numpy as jnp

    cfg, model, n_params, step, flops_per_token = _llama_flagship(
        seq, recompute=True)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    dt, spread, lossv = _time_windows(step, lambda: (ids, ids), iters=10)
    tokens_per_sec = batch * seq / dt
    mfu = flops_per_token * tokens_per_sec / peak
    return {
        "metric": f"llama_420m_seq{seq}_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu": round(mfu, 4), "step_ms": round(dt * 1000, 2),
                  "params": n_params, "loss": round(lossv, 4),
                  "batch": batch, "seq": seq, "peak": peak_kind,
                  "recompute": True, "pipeline": False, "runs": _RUNS,
                  "iters": 10, "spread": round(spread, 4)},
    }


def bench_llama_decode(peak, peak_kind, prefill_len=2048, new_tokens=256,
                       kv_int8=False):
    """Serving/decode throughput (VERDICT r4 missing #3): the flagship's
    compiled prefill program and the one-program lax.scan decode loop
    (models/llama.py decode_programs — parity: AnalysisPredictor +
    FusedMultiTransformer KV-cache decode, fused_transformer.py:994).
    Reports steady-state decode tokens/s at batch 8 as the headline value;
    batch 1 and prefill tokens/s land in extra. Decode is HBM-bound: the
    model-bandwidth utilisation (MBU = bytes-of-weights+cache per token /
    HBM bandwidth) is the honest efficiency number, reported per batch.

    ``kv_int8=True`` is the quantized-serving arm (``llama_decode_int8``,
    SERVING.md "Quantized KV & weights"): int8 weight streaming
    (quantize_for_serving — decode matmuls read int8 codes + per-channel
    scales, dequantized in the matmul epilogue) AND an int8 KV cache
    (codes + per-row fp32 absmax scales). MBU is then computed against
    these *necessary* int8 bytes — the smaller denominator is the whole
    point: the same achieved bandwidth serves ~2x the tokens."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    seq = prefill_len + new_tokens
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=seq, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    if kv_int8:
        from paddle_tpu.quantization import (quantize_for_serving,
                                             serving_state_bytes)
        quantize_for_serving(model, inplace=True)
        weight_bytes = float(serving_state_bytes(model))
    else:
        weight_bytes = 2.0 * n_params
    state = model.state_dict(include_non_persistable_buffer=True)
    rng = np.random.default_rng(0)
    hbm_bw = _HBM_BW[peak_kind]
    per_batch = {}
    for batch in (1, 8):
        prefill, decode, _ = model.decode_programs(batch, prefill_len,
                                                   new_tokens, seq)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (batch, prefill_len)), jnp.int32)
        caches0 = model.init_kv_caches(batch, seq,
                                       dtype="int8" if kv_int8 else None)
        keys = jax.random.split(jax.random.key(0), new_tokens)

        def run_prefill():
            tok, caches = prefill(state, ids, caches0, keys[0])
            return tok

        # prefill timing: whole-prompt forward, 10 iters/window
        t = _time_windows(lambda: run_prefill(), lambda: (), iters=10)
        dt_pre, spread_pre = t[0], t[1]
        tok0, caches1 = prefill(state, ids, caches0, keys[0])

        # decode timing: one call = new_tokens-1 fused steps in one program
        t = _time_windows(lambda: decode(state, tok0, caches1, keys[1:]),
                          lambda: (), iters=3)
        dt_dec, spread_dec = t[0], t[1]
        tok_s_decode = batch * (new_tokens - 1) / dt_dec
        ms_per_tok = dt_dec / (new_tokens - 1) * 1000
        # bytes touched per decode step: all weights + the KV cache read
        # up to the mean filled length + new KV write (negligible). int8
        # KV: codes (kvh*d bytes) + fp32 absmax scales (kvh*4) per
        # token per layer per K/V; bf16: kvh*d*2
        kv_tok = (cfg.num_key_value_heads * (cfg.head_dim + 4) if kv_int8
                  else cfg.num_key_value_heads * cfg.head_dim * 2)
        cache_bytes = (2 * cfg.num_hidden_layers * batch
                       * (prefill_len + new_tokens / 2) * kv_tok)
        cache_bf16 = (2 * cfg.num_hidden_layers * batch
                      * (prefill_len + new_tokens / 2)
                      * cfg.num_key_value_heads * cfg.head_dim * 2)
        mbu = (weight_bytes + cache_bytes) / (dt_dec / (new_tokens - 1)) \
            / hbm_bw
        per_batch[batch] = {
            "step_bytes": round(weight_bytes + cache_bytes),
            "bytes_ratio_vs_bf16": round(
                (2.0 * n_params + cache_bf16)
                / (weight_bytes + cache_bytes), 4),
            "decode_tokens_per_sec": round(tok_s_decode, 1),
            "decode_ms_per_token": round(ms_per_tok, 3),
            "prefill_tokens_per_sec": round(batch * prefill_len / dt_pre, 1),
            "prefill_ms": round(dt_pre * 1000, 2),
            "mbu": round(mbu, 4),
            "spread_prefill": round(spread_pre, 4),
            "spread_decode": round(spread_dec, 4),
        }
    headline = per_batch[8]["decode_tokens_per_sec"]
    sfx = "_int8" if kv_int8 else ""
    return {
        "metric": f"llama_420m_decode{sfx}_tokens_per_sec_batch8",
        "value": headline,
        "unit": "tokens/s",
        # no absolute serving baseline published; report MBU-vs-ideal as
        # the honest ratio (1.0 = every decode step at HBM speed)
        "vs_baseline": per_batch[8]["mbu"],
        "extra": {"params": n_params, "prefill_len": prefill_len,
                  "new_tokens": new_tokens, "batches": per_batch,
                  "kv_int8": kv_int8,
                  "bytes_ratio_vs_bf16": per_batch[8]["bytes_ratio_vs_bf16"],
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "mbu_note": "MBU vs the SPEC bandwidth; this chip's "
                              "measured streaming ceiling is ~600 GB/s "
                              "(PROFILE_resnet50.md), against which the "
                              "batch-8 decode is ~bandwidth-bound",
                  "pipeline": False, "runs": _RUNS,
                  "spread": per_batch[8]["spread_decode"]},
    }


def _make_tracer(trace_path):
    """Tracer for the serving configs when ``--trace PATH`` was given
    (None otherwise — tracing stays off and the engine holds the no-op
    NULL_TRACER)."""
    if trace_path is None:
        return None
    from paddle_tpu.observability import Tracer
    return Tracer()


def _dump_trace(tracer, trace_path, name):
    """Write the config's Chrome trace next to ``trace_path`` with the
    config name spliced in before the extension (two serving configs in
    one run must not clobber each other); returns the written path."""
    if tracer is None:
        return None
    import os
    root, ext = os.path.splitext(trace_path)
    return tracer.dump_chrome_trace(f"{root}.{name}{ext or '.json'}")


def bench_llama_serving(peak, peak_kind, n_requests=12, max_new_tokens=64,
                        trace_path=None, quantized=False):
    """Continuous-batching serving throughput (SERVING.md): the paged
    KV-pool engine (paddle_tpu.serving) driven by a staggered-arrival
    trace — 2 requests queued at t=0, then one more every 4 engine steps,
    ragged prompt lengths in [64, 256). Headline value is end-to-end
    generated tokens/s; TTFT p50/p99 and TPOT land in extra (and in the
    bench_summary cell — the driver's serving SLO view). Programs are
    warmed on a throwaway trace first so compile time doesn't pollute
    TTFT; the measured trace reuses the same engine (decode stays ONE
    compiled program throughout — asserted, it is the design contract).

    ``quantized=True`` is the int8 arm (``llama_serving_int8``,
    SERVING.md "Quantized KV & weights"): the engine's paged pool stores
    int8 KV codes + per-row fp32 absmax scales and the decode matmuls
    stream int8 weights (quantize_for_serving). The weights-only MBU
    floor is computed against the *necessary* int8 bytes
    (serving_state_bytes) — smaller denominator, same achieved
    bandwidth, ~2x the tokens."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine, ServingMetrics

    name = "llama_serving_int8" if quantized else "llama_serving"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    if quantized:
        from paddle_tpu.quantization import (quantize_for_serving,
                                             serving_state_bytes)
        quantize_for_serving(model, inplace=True)
        weight_bytes = float(serving_state_bytes(model))
    else:
        weight_bytes = 2.0 * n_params
    rng = np.random.default_rng(0)
    lens = [int(x) for x in rng.integers(64, 256, n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    tracer = _make_tracer(trace_path)
    eng = ServingEngine(model, num_pages=512, page_size=16, max_slots=8,
                        max_pages_per_slot=32, tracer=tracer,
                        kv_quant=quantized)
    # warm BOTH step-shape programs (decode + mixed) with one all-slots-
    # inactive dispatch each — prompts of any length reuse them (chunks
    # are array values, not shapes), so no per-length warm sweep remains
    eng.warm_programs()
    eng.metrics = ServingMetrics()  # compile time stays out of the trace
    eng.metrics.set_kv_quant(quantized)  # re-arm after the reset
    eng.metrics.set_slo(**_SERVING_SLOS[name])

    added = 2
    for p in prompts[:2]:
        eng.add_request(p, max_new_tokens)
    steps = 0
    while eng.scheduler.has_work() or added < n_requests:
        eng.step()
        steps += 1
        if added < n_requests and steps % 4 == 0:
            eng.add_request(prompts[added], max_new_tokens)
            added += 1
    m = eng.metrics.summary()
    assert eng.decode_program_count() == 1, "serving decode retraced"
    hbm_bw = _HBM_BW[peak_kind]
    # weights-only traffic floor: every engine step streams the weights
    # once regardless of slot occupancy (KV traffic excluded — honest
    # lower bound on bandwidth utilisation). int8 arm: the necessary
    # bytes are the int8 codes + scales, about half the bf16 stream
    wall = max(m["wall_s"], 1e-9)
    mbu = steps * weight_bytes / wall / hbm_bw
    # necessary-bytes-per-decode-step decomposition at full occupancy
    # (PERF.md): weights once + the 8 slots' mean live context of KV.
    # The ratio vs the bf16 arm is the bandwidth headroom int8 buys.
    kv_tok = eng.pool.kv_bytes_per_token()
    kv_tok_bf16 = (2 * cfg.num_hidden_layers * cfg.num_key_value_heads
                   * cfg.head_dim * 2)
    mean_ctx = sum(lens) / len(lens) + max_new_tokens / 2
    step_bytes = weight_bytes + 8 * mean_ctx * kv_tok
    step_bytes_bf16 = 2.0 * n_params + 8 * mean_ctx * kv_tok_bf16
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": f"llama_420m_{'serving_int8' if quantized else 'serving'}"
                  f"_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mbu, 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "kv_quant": int(quantized),
                  "kv_quant_err_bound": round(m["kv_quant_err_bound"], 6),
                  "kv_bytes_per_token": kv_tok,
                  "step_bytes": round(step_bytes),
                  "bytes_ratio_vs_bf16": round(step_bytes_bf16
                                               / step_bytes, 4),
                  "max_new_tokens": max_new_tokens,
                  "prompt_lens": lens, "engine_steps": steps,
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "timed_out": m["timed_out"],
                  "quarantined": m["quarantined"],
                  "queue_wait_p99": round(m["queue_wait_p99_s"], 4),
                  "kv_util_peak": round(m["kv_util_peak"], 4),
                  "queue_depth_max": m["queue_depth_max"],
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "slo": _SERVING_SLOS[name],
                  "retraces": eng.decode_program_count() - 1,
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_prefix(peak, peak_kind, n_requests=12,
                               max_new_tokens=64, prefix_len=384,
                               trace_path=None):
    """Prefix-cache serving throughput (SERVING.md "Prefix caching"):
    same engine/model/arrival shape as bench_llama_serving, but every
    request shares a ``prefix_len``-token system prompt followed by a
    short ragged user suffix in [16, 64) — the chat-serving workload the
    prefix cache targets. The first request prefills and registers the
    shared pages; the staggered followers map them and prefill only
    their suffix, so TTFT collapses toward a single small-bucket prefill
    and ``cache_hit_rate`` (fraction of prefill context tokens served
    from cached pages) lands in the bench_summary cell next to
    ttft_p50/p99. Decode stays ONE compiled program (asserted) — the
    cached-prefix offset is a traced argument, never a bucket axis."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine, ServingMetrics

    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    sfx_lens = [int(x) for x in rng.integers(16, 64, n_requests)]
    prompts = [np.concatenate(
        [system, rng.integers(0, cfg.vocab_size, n).astype(np.int32)])
        for n in sfx_lens]
    lens = [len(p) for p in prompts]
    tracer = _make_tracer(trace_path)
    eng = ServingEngine(model, num_pages=512, page_size=16, max_slots=8,
                        max_pages_per_slot=48, tracer=tracer)
    # warm both step-shape programs with scratch-page dispatches: writes
    # nothing into the pool and registers nothing, so the measured trace
    # starts with a cold prefix index for its own system prompt
    eng.warm_programs()
    eng.metrics = ServingMetrics()  # compile time stays out of the trace
    eng.metrics.set_slo(**_SERVING_SLOS["llama_serving_prefix"])

    added = 2
    for p in prompts[:2]:
        eng.add_request(p, max_new_tokens)
    steps = 0
    while eng.scheduler.has_work() or added < n_requests:
        eng.step()
        steps += 1
        if added < n_requests and steps % 4 == 0:
            eng.add_request(prompts[added], max_new_tokens)
            added += 1
    m = eng.metrics.summary()
    assert eng.decode_program_count() == 1, "serving decode retraced"
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = steps * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, "llama_serving_prefix")
    return {
        "metric": "llama_420m_serving_prefix_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mbu, 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "max_new_tokens": max_new_tokens,
                  "prefix_len": prefix_len, "prompt_lens": lens,
                  "engine_steps": steps,
                  "cache_hit_rate": round(m["cache_hit_rate"], 4),
                  "prefill_tokens": m["prefill_tokens"],
                  "prefill_cached_tokens": m["prefill_cached_tokens"],
                  "prefix_hits": m.get("prefix_hits", 0),
                  "prefix_evictions": m.get("prefix_evictions", 0),
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "timed_out": m["timed_out"],
                  "quarantined": m["quarantined"],
                  "kv_util_peak": round(m["kv_util_peak"], 4),
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "slo": _SERVING_SLOS["llama_serving_prefix"],
                  "retraces": eng.decode_program_count() - 1,
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_chunked(peak, peak_kind, n_short=10, n_long=2,
                                max_new_tokens=48, long_len=768,
                                budget=128, trace_path=None):
    """Chunked-prefill serving A/B (SERVING.md "Chunked prefill & mixed
    steps"): a decode-heavy short-request stream with LONG prompts
    landing mid-trace, run twice on the same model — chunked OFF (the
    legacy whole-prompt admission prefill: a long arrival stalls every
    decoding slot for its entire prompt) and chunked ON (the prompt
    streams through the mixed program in budget-sized chunks alongside
    the decode rows, so decoders keep emitting every step). Headline
    value is the chunked arm's tokens/s; the A/B evidence the driver
    wants is ``itl_p99`` and ``goodput_at_slo`` for BOTH arms in the
    bench_summary cell — head-of-line blocking shows up as the OFF
    arm's inter-token p99, which is exactly what chunking removes.
    Greedy streams are asserted token-exact between the arms (chunk
    boundaries are scheduling, never semantics), and both arms assert
    zero retraces across the decode + mixed program pair."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine, ServingMetrics

    name = "llama_serving_chunked"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    rng = np.random.default_rng(0)
    short_lens = [int(x) for x in rng.integers(48, 96, n_short)]
    shorts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
              for n in short_lens]
    longs = [rng.integers(0, cfg.vocab_size, long_len).astype(np.int32)
             for _ in range(n_long)]
    long_steps = [6 + 10 * i for i in range(n_long)]  # land mid-decode
    tracer = _make_tracer(trace_path)

    def run_arm(chunked):
        eng = ServingEngine(model, num_pages=512, page_size=16,
                            max_slots=8, max_pages_per_slot=64,
                            prefill_token_budget=budget,
                            tracer=tracer if chunked else None,
                            chunked=chunked, prefill_chunk=64)
        eng.warm_programs()
        eng.metrics = ServingMetrics()  # compile stays out of the trace
        eng.metrics.set_chunked(chunked)  # re-arm after the reset
        eng.metrics.set_slo(**_SERVING_SLOS[name])

        added, added_long = 2, 0
        rids = [eng.add_request(p, max_new_tokens) for p in shorts[:2]]
        steps = 0
        while (eng.scheduler.has_work() or added < n_short
               or added_long < n_long):
            eng.step()
            steps += 1
            if added < n_short and steps % 3 == 0:
                rids.append(eng.add_request(shorts[added],
                                            max_new_tokens))
                added += 1
            if added_long < n_long and steps >= long_steps[added_long]:
                # a long prompt arrives while every slot is decoding
                rids.append(eng.add_request(longs[added_long], 8))
                added_long += 1
        outs = [list(eng.request(r).tokens) for r in rids]
        m = eng.metrics.summary()
        retraces = sum(n - 1 for n in eng.step_program_counts().values())
        assert retraces == 0, "serving step program retraced"
        return eng, m, steps, outs

    _, m0, steps0, outs0 = run_arm(False)
    eng, m, steps, outs = run_arm(True)
    # the tentpole's determinism contract, priced into the headline:
    # chunked streams are token-exact vs whole-prompt prefill
    assert outs == outs0, "chunked arm diverged from whole-prompt arm"
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = steps * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_chunked_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(m["tokens_per_s"]
                             / max(m0["tokens_per_s"], 1e-9), 4),
        "extra": {"params": n_params,
                  "n_short": n_short, "n_long": n_long,
                  "short_lens": short_lens, "long_len": long_len,
                  "prefill_chunk": 64, "prefill_token_budget": budget,
                  "max_new_tokens": max_new_tokens,
                  "engine_steps": steps,
                  "engine_steps_baseline": steps0,
                  "tokens_per_s_baseline": round(m0["tokens_per_s"], 1),
                  "mixed_steps": m["mixed_steps"],
                  "chunk_tokens_total": m["chunk_tokens_total"],
                  "chunks_dispatched": m["chunks_dispatched_total"],
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "itl_p99_baseline": round(m0["itl_p99_s"], 5),
                  "itl_p99_ratio": round(
                      m0["itl_p99_s"] / max(m["itl_p99_s"], 1e-9), 4),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "timed_out": m["timed_out"],
                  "quarantined": m["quarantined"],
                  "kv_util_peak": round(m["kv_util_peak"], 4),
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_baseline": round(
                      m0["goodput_at_slo"], 4),
                  "slo": _SERVING_SLOS[name],
                  "retraces": sum(
                      n - 1
                      for n in eng.step_program_counts().values()),
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_spec(peak, peak_kind, n_requests=12,
                             max_new_tokens=64, prefix_len=256,
                             spec_k=4, trace_path=None):
    """Speculative-decoding serving A/B (SERVING.md "Speculative
    decoding"): the shared-system-prompt staggered trace run twice on
    the same model — spec-off (plain decode) then spec-on (n-gram
    prompt-lookup draft + one fixed-shape ``[max_slots, k]`` verify
    program). Headline value is the spec-on tokens/s; the baseline
    arm's tokens/s and the speedup land in extra alongside
    ``accept_rate`` / ``draft_hit_rate`` (the knobs that explain the
    speedup: every accepted draft token is one decode step's weight
    stream the engine did not pay for). Greedy output is asserted
    token-exact between the arms — speculation changes how many tokens
    a step emits, never which — and both per-step-shape programs are
    asserted compiled-once (both programs are warmed by
    ``warm_programs()`` — verify rows ride the mixed program — so
    mid-trace compiles stay out of TTFT)."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (ServingEngine, ServingMetrics,
                                    SpeculativeConfig)

    name = "llama_serving_spec"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    sfx_lens = [int(x) for x in rng.integers(16, 64, n_requests)]
    prompts = [np.concatenate(
        [system, rng.integers(0, cfg.vocab_size, n).astype(np.int32)])
        for n in sfx_lens]
    lens = [len(p) for p in prompts]
    tracer = _make_tracer(trace_path)

    def run_arm(spec_on):
        eng = ServingEngine(model, num_pages=512, page_size=16,
                            max_slots=8, max_pages_per_slot=48,
                            tracer=tracer if spec_on else None,
                            speculative=(SpeculativeConfig(k=spec_k)
                                         if spec_on else None))
        # verify rows share the mixed program with prefill chunks, so
        # one warm dispatch per step shape covers spec-on and -off alike
        # (no propose-always warm drafter needed anymore)
        eng.warm_programs()
        eng.metrics = ServingMetrics()  # compile stays out of the trace
        eng.metrics.set_spec(spec_on)   # re-arm after the reset
        eng.metrics.set_slo(**_SERVING_SLOS[name])

        added = 2
        rids = [eng.add_request(p, max_new_tokens) for p in prompts[:2]]
        steps = 0
        while eng.scheduler.has_work() or added < n_requests:
            eng.step()
            steps += 1
            if added < n_requests and steps % 4 == 0:
                rids.append(eng.add_request(prompts[added],
                                            max_new_tokens))
                added += 1
        outs = [list(eng.request(r).tokens) for r in rids]
        m = eng.metrics.summary()
        retraces = sum(n - 1 for n in eng.step_program_counts().values())
        assert retraces == 0, "serving step program retraced"
        return eng, m, steps, outs

    _, m0, steps0, outs0 = run_arm(False)
    eng, m, steps, outs = run_arm(True)
    # the determinism contract, priced into the headline number: the
    # speculative arm's greedy streams are token-exact vs plain decode
    assert outs == outs0, "speculative arm diverged from plain decode"
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = steps * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_spec_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(m["tokens_per_s"]
                             / max(m0["tokens_per_s"], 1e-9), 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "max_new_tokens": max_new_tokens,
                  "prefix_len": prefix_len, "prompt_lens": lens,
                  "spec_k": spec_k,
                  "engine_steps": steps,
                  "engine_steps_baseline": steps0,
                  "tokens_per_s_baseline": round(m0["tokens_per_s"], 1),
                  "speedup_vs_decode": round(
                      m["tokens_per_s"] / max(m0["tokens_per_s"], 1e-9),
                      4),
                  "accept_rate": round(m["spec_accept_rate"], 4),
                  "draft_hit_rate": round(m["spec_draft_hit_rate"], 4),
                  "spec_draft_tokens": m["spec_draft_tokens_total"],
                  "spec_accepted_tokens": m["spec_accepted_tokens_total"],
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "timed_out": m["timed_out"],
                  "quarantined": m["quarantined"],
                  "kv_util_peak": round(m["kv_util_peak"], 4),
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "slo": _SERVING_SLOS[name],
                  "retraces": sum(
                      n - 1
                      for n in eng.step_program_counts().values()),
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_fleet(peak, peak_kind, n_requests=12,
                              max_new_tokens=64, kill_step=20,
                              trace_path=None):
    """Fault-tolerant fleet serving (SERVING.md "Engine fleet &
    failover"): the same 420M model and staggered-arrival trace as
    bench_llama_serving, but behind a 2-replica ``FleetRouter`` — and
    one replica is KILLED mid-run (router step ``kill_step``). Its
    in-flight requests fail over to the survivor, replay their already
    streamed positions (suppressed by the exactly-once dedup) and then
    finish; the headline tokens/s is the CLIENT-visible stream, so the
    replay overhead is priced in. failovers / replayed_tokens / shed
    land in the bench_summary cell — the driver's evidence that failover
    happened and what it cost against the serving SLOs."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (FleetMetrics, FleetRouter,
                                    ServingEngine, ServingMetrics)

    name = "llama_serving_fleet"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    weight_bytes = 2.0 * n_params
    rng = np.random.default_rng(0)
    lens = [int(x) for x in rng.integers(64, 256, n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    tracer = _make_tracer(trace_path)
    engines = [ServingEngine(model, num_pages=256, page_size=16,
                             max_slots=8, max_pages_per_slot=32,
                             tracer=tracer)
               for _ in range(2)]
    # both replicas share the model, so the compiled decode/mixed
    # programs are shared too — warm them once through replica 0, plus
    # one tiny run on replica 1 so its own step path is exercised
    engines[0].warm_programs()
    engines[1].add_request(prompts[0], 2)
    engines[1].run_to_completion(max_steps=100)
    warm_steps = [e.stats()["steps"] for e in engines]

    router = FleetRouter(engines, tracer=tracer)
    router.metrics = ServingMetrics()  # compile time stays out of the trace
    router.metrics.set_slo(**_SERVING_SLOS[name])
    router.fleet_metrics = FleetMetrics()

    added = 2
    for p in prompts[:2]:
        router.submit(p, max_new_tokens)
    steps = 0
    killed = False
    while router.has_work() or added < n_requests:
        router.step()
        steps += 1
        if not killed and steps == kill_step:
            router.kill_replica(1)  # chaos: replica 1 dies mid-decode
            killed = True
        if added < n_requests and steps % 4 == 0:
            router.submit(prompts[added], max_new_tokens)
            added += 1
    m = router.metrics.summary()
    fleet = router.fleet_metrics.summary()
    survivors = [e for e, rep in zip(engines, router._replicas)
                 if rep.state != "dead"]
    for e in survivors:
        assert e.decode_program_count() == 1, "serving decode retraced"
    engine_steps = sum(e.stats()["steps"] - w
                       for e, w in zip(engines, warm_steps))
    hbm_bw = _HBM_BW[peak_kind]
    # weights-only floor across BOTH replicas' engine steps: every step
    # on every live replica streams the (shared) weights once
    wall = max(m["wall_s"], 1e-9)
    mbu = engine_steps * weight_bytes / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_fleet_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mbu, 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "max_new_tokens": max_new_tokens,
                  "prompt_lens": lens,
                  "replicas": 2, "kill_step": kill_step,
                  "replicas_ejected": 2 - router.replicas_live(),
                  "router_steps": steps, "engine_steps": engine_steps,
                  "failovers": fleet["failovers"],
                  "replayed_requests": fleet["replayed_requests"],
                  "replayed_tokens": fleet["replayed_tokens"],
                  "shed": fleet["shed"],
                  "breaker_opens": fleet["breaker_opens"],
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "slo": _SERVING_SLOS[name],
                  "retraces": sum(e.decode_program_count() - 1
                                  for e in survivors),
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_failover(peak, peak_kind, n_requests=12,
                                 max_new_tokens=64, kill_step=20,
                                 snapshot_interval=4, trace_path=None):
    """Bounded-replay failover A/B (RESILIENCE.md "Serving recovery
    playbook"): the same 420M model, staggered trace and mid-run replica
    kill as bench_llama_serving_fleet, run twice. Arm A has no snapshot
    store, so every failed-over request replays its FULL already-emitted
    stream on the survivor; arm B's replicas share a ``SnapshotStore``
    (capture every ``snapshot_interval`` engine steps), so failover
    restores each request's KV from its latest verified snapshot and
    replays only the tokens emitted since. Both arms see the identical
    trace and must produce bitwise-identical client streams (asserted) —
    the cell's evidence is the replay-work delta:
    ``replayed_tokens_full`` vs ``recovery_replayed_tokens`` +
    ``recovery_restored_tokens``, with ``goodput_at_slo`` for both arms
    so the saved recompute is priced against the same SLOs."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (FleetMetrics, FleetRouter,
                                    ServingEngine, ServingMetrics,
                                    SnapshotStore)

    name = "llama_serving_failover"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    weight_bytes = 2.0 * n_params
    rng = np.random.default_rng(0)
    lens = [int(x) for x in rng.integers(64, 256, n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    tracer = _make_tracer(trace_path)

    def _arm(bounded):
        # replicas share the model, so compiled programs are shared
        # across arms too — arm A pays the compiles, arm B reuses them
        store = SnapshotStore() if bounded else None
        kw = ({"snapshot_store": store,
               "snapshot_interval": snapshot_interval} if bounded else {})
        arm_tracer = tracer if bounded else None
        engines = [ServingEngine(model, num_pages=256, page_size=16,
                                 max_slots=8, max_pages_per_slot=32,
                                 tracer=arm_tracer, **kw)
                   for _ in range(2)]
        engines[0].warm_programs()
        engines[1].add_request(prompts[0], 2)
        engines[1].run_to_completion(max_steps=100)
        warm_steps = [e.stats()["steps"] for e in engines]
        router = FleetRouter(engines, tracer=arm_tracer)
        router.metrics = ServingMetrics()  # compile time stays out
        router.metrics.set_slo(**_SERVING_SLOS[name])
        router.fleet_metrics = FleetMetrics()
        added = 2
        for p in prompts[:2]:
            router.submit(p, max_new_tokens)
        steps = 0
        killed = False
        out = {}
        while router.has_work() or added < n_requests:
            for ev in router.step():
                if ev.get("token") is not None:
                    out.setdefault(ev["rid"], []).append(ev["token"])
            steps += 1
            if not killed and steps == kill_step:
                router.kill_replica(1)  # the same chaos in both arms
                killed = True
            if added < n_requests and steps % 4 == 0:
                router.submit(prompts[added], max_new_tokens)
                added += 1
        survivors = [e for e, rep in zip(engines, router._replicas)
                     if rep.state != "dead"]
        for e in survivors:
            assert e.decode_program_count() == 1, "serving decode retraced"
            e.audit_pool()
        engine_steps = sum(e.stats()["steps"] - w
                           for e, w in zip(engines, warm_steps))
        return {"m": router.metrics.summary(),
                "fleet": router.fleet_metrics.summary(),
                "out": out, "steps": steps, "engine_steps": engine_steps,
                "retraces": sum(e.decode_program_count() - 1
                                for e in survivors),
                "ejected": 2 - router.replicas_live()}

    full = _arm(bounded=False)
    bnd = _arm(bounded=True)
    # the whole point of bounded replay: the client streams are the SAME
    assert bnd["out"] == full["out"], \
        "bounded-replay arm diverged from full-replay arm"
    m, fleet = bnd["m"], bnd["fleet"]
    m0, fleet0 = full["m"], full["fleet"]
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = bnd["engine_steps"] * weight_bytes / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_failover_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(m["tokens_per_s"]
                             / max(m0["tokens_per_s"], 1e-9), 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "max_new_tokens": max_new_tokens,
                  "prompt_lens": lens,
                  "replicas": 2, "kill_step": kill_step,
                  "snapshot_interval": snapshot_interval,
                  "replicas_ejected": bnd["ejected"],
                  "router_steps": bnd["steps"],
                  "engine_steps": bnd["engine_steps"],
                  "failovers": fleet["failovers"],
                  # the A/B evidence: replay work in each arm
                  "replayed_tokens": fleet["replayed_tokens"],
                  "replayed_tokens_full": fleet0["replayed_tokens"],
                  "snapshot_restores": fleet["snapshot_restores"],
                  "snapshot_fallbacks": fleet["snapshot_fallbacks"],
                  "recovery_restored_tokens":
                      fleet["recovery_restored_tokens"],
                  "recovery_replayed_tokens":
                      fleet["recovery_replayed_tokens"],
                  "token_exact": True,
                  "shed": fleet["shed"],
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_full": round(m0["goodput_at_slo"], 4),
                  "tokens_per_s_full": round(m0["tokens_per_s"], 1),
                  "slo": _SERVING_SLOS[name],
                  "retraces": bnd["retraces"] + full["retraces"],
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_partition(peak, peak_kind, n_requests=12,
                                  max_new_tokens=48, partition_step=12,
                                  trace_path=None):
    """Clean-vs-lossy wire A/B (SERVING.md "Fleet transport &
    membership"): the same 420M model and staggered trace served by a
    3-replica FleetRouter twice. Arm A runs on the default
    ``LoopbackTransport`` (lossless, synchronous). Arm B routes every
    router<->replica message through a seeded ``ChaosTransport`` —
    drops, duplicates, deterministic reordering — and two-way
    partitions replica 2 at ``partition_step`` until its lease expires,
    the router ejects it and replays its requests on the survivors; the
    partition then heals and the zombie's held traffic must be fenced.
    Both arms must produce bitwise-identical client streams (asserted —
    the exactly-once contract priced by this cell), so the evidence is
    what the lossy wire cost: ``failovers``, ``stale_epoch_discarded``,
    ``duplicates_suppressed``, transport drop volume, and
    ``goodput_at_slo`` for both arms."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (ChaosTransport, FleetMetrics,
                                    FleetRouter, ServingEngine,
                                    ServingMetrics)

    name = "llama_serving_partition"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    weight_bytes = 2.0 * n_params
    rng = np.random.default_rng(0)
    lens = [int(x) for x in rng.integers(64, 256, n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    tracer = _make_tracer(trace_path)

    def _arm(lossy):
        wire = None
        if lossy:
            wire = ChaosTransport(seed=42, drop_p=0.05, dup_p=0.15,
                                  reorder=True)
            wire.partition("router", "replica:2", two_way=True,
                           start=partition_step)
        arm_tracer = tracer if lossy else None
        engines = [ServingEngine(model, num_pages=256, page_size=16,
                                 max_slots=8, max_pages_per_slot=32,
                                 tracer=arm_tracer) for _ in range(3)]
        engines[0].warm_programs()
        engines[1].add_request(prompts[0], 2)
        engines[1].run_to_completion(max_steps=100)
        warm_steps = [e.stats()["steps"] for e in engines]
        router = FleetRouter(engines, tracer=arm_tracer, transport=wire,
                             lease_steps=6)
        router.metrics = ServingMetrics()  # compile time stays out
        router.metrics.set_slo(**_SERVING_SLOS[name])
        router.fleet_metrics = FleetMetrics()
        added = 2
        for p in prompts[:2]:
            router.submit(p, max_new_tokens)
        steps = 0
        out = {}
        while router.has_work() or added < n_requests:
            for ev in router.step():
                if ev.get("token") is not None:
                    out.setdefault(ev["rid"], []).append(ev["token"])
            steps += 1
            if added < n_requests and steps % 4 == 0:
                router.submit(prompts[added], max_new_tokens)
                added += 1
            assert steps < 5000, "fleet hung on the lossy wire"
        if lossy:
            wire.heal()      # the zombie's held traffic arrives ...
            for ev in router.step():  # ... and must be fenced, not
                if ev.get("token") is not None:   # re-emitted
                    out.setdefault(ev["rid"], []).append(ev["token"])
            steps += 1
        survivors = [e for e, rep in zip(engines, router._replicas)
                     if rep.state != "dead"]
        for e in survivors:
            assert e.decode_program_count() == 1, "serving decode retraced"
            e.audit_pool()
        engine_steps = sum(e.stats()["steps"] - w
                           for e, w in zip(engines, warm_steps))
        return {"m": router.metrics.summary(),
                "fleet": router.fleet_metrics.summary(),
                "wire": dict(router.transport.stats()),
                "out": out, "steps": steps, "engine_steps": engine_steps,
                "retraces": sum(e.decode_program_count() - 1
                                for e in survivors),
                "ejected": 3 - router.replicas_live()}

    clean = _arm(lossy=False)
    lossy = _arm(lossy=True)
    # the exactly-once contract: the lossy wire may cost latency and
    # replay work, never tokens — streams identical to the clean arm
    assert lossy["out"] == clean["out"], \
        "lossy-wire arm diverged from the clean arm"
    m, fleet, wire = lossy["m"], lossy["fleet"], lossy["wire"]
    m0, fleet0 = clean["m"], clean["fleet"]
    assert wire["corrupt_dropped"] == wire["corrupt_injected"]
    assert fleet["lease_expirations"] >= 1, "the partition never expired"
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = lossy["engine_steps"] * weight_bytes / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_partition_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(m["tokens_per_s"]
                             / max(m0["tokens_per_s"], 1e-9), 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "max_new_tokens": max_new_tokens,
                  "prompt_lens": lens,
                  "replicas": 3, "partition_step": partition_step,
                  "replicas_ejected": lossy["ejected"],
                  "router_steps": lossy["steps"],
                  "engine_steps": lossy["engine_steps"],
                  # the A/B evidence: what the lossy wire cost
                  "failovers": fleet["failovers"],
                  "failovers_clean": fleet0["failovers"],
                  "stale_epoch_discarded": fleet["stale_epoch_discarded"],
                  "lease_expirations": fleet["lease_expirations"],
                  "duplicates_suppressed": fleet["duplicates_suppressed"],
                  "replayed_tokens": fleet["replayed_tokens"],
                  "transport_dropped": wire["dropped"],
                  "transport_duplicated": wire["duplicated"],
                  "transport_held": wire["held"],
                  "token_exact": True,
                  "shed": fleet["shed"],
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_clean": round(m0["goodput_at_slo"], 4),
                  "tokens_per_s_clean": round(m0["tokens_per_s"], 1),
                  "slo": _SERVING_SLOS[name],
                  "retraces": lossy["retraces"] + clean["retraces"],
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_multihost(peak, peak_kind, n_requests=12,
                                  max_new_tokens=48, trace_path=None):
    """Loopback-vs-socket wire A/B (SERVING.md "Multi-host serving"):
    the same 420M model and staggered trace served by a 2-replica
    FleetRouter twice. Arm A is the default in-process
    ``LoopbackTransport``. Arm B puts every router<->replica message on
    a REAL localhost TCP socket — length-prefixed frames through
    ``SocketTransport``, each replica's ``EngineServer`` behind its own
    dialed connection, exactly the wire ``spawn_fleet`` replicas speak
    (the engines stay in-process so the chip is allocated once; the
    process boundary itself is priced by tools/profile_serving.py
    --multihost). Both arms must produce bitwise-identical client
    streams (asserted), so the evidence is what the socket costs:
    frame/byte volume, reconnects (0 on a healthy wire),
    lease_expirations (0 — framing latency must never masquerade as
    membership churn), and ``goodput_at_slo`` for both arms."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (FleetMetrics, FleetRouter,
                                    ServingEngine, ServingMetrics,
                                    SocketTransport)
    from paddle_tpu.serving.transport import EngineServer

    name = "llama_serving_multihost"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    weight_bytes = 2.0 * n_params
    rng = np.random.default_rng(0)
    lens = [int(x) for x in rng.integers(64, 256, n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    tracer = _make_tracer(trace_path)

    class _RemoteFront:
        """Engine-shaped stand-in the router holds on the socket arm —
        the real EngineServer answers from the far end of the wire."""
        is_remote = True
        snapshot_store = None
        flight_recorder = None
        pool = None

        def __init__(self, idx):
            self.idx = idx

    def _arm(socket_wire):
        arm_tracer = tracer if socket_wire else None
        engines = [ServingEngine(model, num_pages=256, page_size=16,
                                 max_slots=8, max_pages_per_slot=32,
                                 tracer=arm_tracer) for _ in range(2)]
        for e in engines:
            e.warm_programs()
        warm_steps = [e.stats()["steps"] for e in engines]
        reps = []
        if socket_wire:
            wire = SocketTransport("router", listen=("127.0.0.1", 0),
                                   poll_s=0.0005, query_timeout_s=0.01)
            for i, e in enumerate(engines):
                tr = SocketTransport(
                    f"replica:{i}", connect={"router": wire.listen_addr},
                    poll_s=0.0005)
                reps.append((tr, EngineServer(i, e, tr)))
            want = {f"replica:{i}" for i in range(2)}
            deadline = time.monotonic() + 30
            while set(wire.peers()) != want:
                for tr, _ in reps:
                    tr.pump()
                wire.pump()
                assert time.monotonic() < deadline, "fleet never formed"
            router = FleetRouter([_RemoteFront(i) for i in range(2)],
                                 transport=wire, tracer=arm_tracer,
                                 lease_steps=60)
        else:
            router = FleetRouter(engines, tracer=arm_tracer,
                                 lease_steps=60)
        router.metrics = ServingMetrics()  # compile time stays out
        router.metrics.set_slo(**_SERVING_SLOS[name])
        router.fleet_metrics = FleetMetrics()
        added = 2
        for p in prompts[:2]:
            router.submit(p, max_new_tokens)
        steps = 0
        out = {}
        while router.has_work() or added < n_requests:
            for ev in router.step():
                if ev.get("token") is not None:
                    out.setdefault(ev["rid"], []).append(ev["token"])
            for tr, _ in reps:
                tr.pump()
            steps += 1
            if added < n_requests and steps % 4 == 0:
                router.submit(prompts[added], max_new_tokens)
                added += 1
            assert steps < 20000, "multi-host fleet hung"
        for e in engines:
            assert e.decode_program_count() == 1, "serving decode retraced"
            e.audit_pool()
        engine_steps = sum(e.stats()["steps"] - w
                           for e, w in zip(engines, warm_steps))
        res = {"m": router.metrics.summary(),
               "fleet": router.fleet_metrics.summary(),
               "wire": dict(router.transport.stats()),
               "out": out, "steps": steps, "engine_steps": engine_steps,
               "retraces": sum(e.decode_program_count() - 1
                               for e in engines)}
        if socket_wire:
            for tr, _ in reps:
                tr.close()
            wire.close()
        return res

    loop = _arm(socket_wire=False)
    sock = _arm(socket_wire=True)
    # the framing contract: the socket wire may cost syscalls and
    # latency, never tokens — streams identical to the loopback arm
    assert sock["out"] == loop["out"], \
        "socket arm diverged from the loopback arm"
    assert len(sock["out"]) == n_requests
    m, fleet, wire = sock["m"], sock["fleet"], sock["wire"]
    m0 = loop["m"]
    assert wire["corrupt_dropped"] == 0, "a damaged frame was injected?"
    assert fleet["lease_expirations"] == 0, \
        "socket latency expired a lease on a healthy wire"
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = sock["engine_steps"] * weight_bytes / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_multihost_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(m["tokens_per_s"]
                             / max(m0["tokens_per_s"], 1e-9), 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "max_new_tokens": max_new_tokens,
                  "prompt_lens": lens,
                  "replicas": 2,
                  "router_steps": sock["steps"],
                  "engine_steps": sock["engine_steps"],
                  # the A/B evidence: what the socket wire cost
                  "frames_sent": wire["socket_frames_sent"],
                  "frames_recv": wire["socket_frames_recv"],
                  "frame_bytes_sent": wire["socket_bytes_sent"],
                  "frame_bytes_recv": wire["socket_bytes_recv"],
                  "socket_reconnects": wire["socket_reconnects"],
                  "lease_expirations": fleet["lease_expirations"],
                  "duplicates_suppressed": fleet["duplicates_suppressed"],
                  "token_exact": True,
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_loopback": round(
                      m0["goodput_at_slo"], 4),
                  "tokens_per_s_loopback": round(m0["tokens_per_s"], 1),
                  "slo": _SERVING_SLOS[name],
                  "retraces": sock["retraces"] + loop["retraces"],
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_tiered(peak, peak_kind, n_requests=12,
                               max_new_tokens=48, trace_path=None):
    """Tiered-KV serving A/B (SERVING.md "KV tiering & traffic
    harness"): a seeded Poisson multi-tenant :class:`Workload` (Zipf
    tenant popularity over 3 shared system prompts, mixed suffix
    lengths) replayed on a pool deliberately sized to hold ~1.3 tenants'
    pages, so returning tenants force LRU evictions. Arm A runs with no
    host tier (evicted = recompute); arm B attaches a :class:`HostTier`
    so evictions demote to host RAM and hits restore. Both arms see the
    IDENTICAL trace (the workload is a value) and each arm replays it
    twice on one engine — epoch 1 warms the compiled programs and the
    prefix index, epoch 2 is measured — so the goodput_at_slo and
    HBM/host/miss hit-rate deltas in the bench_summary cell are
    attributable to the tier alone. Decode stays ONE compiled program
    per arm: restores are admission-time ``device_put``s, never a new
    step shape."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (HostTier, ServingEngine,
                                    ServingMetrics, make_workload)

    name = "llama_serving_tiered"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    wl = make_workload(seed=0, n_requests=n_requests, arrival="poisson",
                       rate=0.5, tenants=3, zipf_alpha=1.2,
                       system_len=(160, 224),
                       prompt_mix=((0.7, 16, 48), (0.3, 48, 96)),
                       max_new=(max_new_tokens, max_new_tokens),
                       vocab_size=cfg.vocab_size)
    tracer = _make_tracer(trace_path)
    arms = {}
    for arm, tier in (("notier", None), ("tiered", HostTier())):
        eng = ServingEngine(model, num_pages=40, page_size=16,
                            max_slots=4, tracer=tracer, host_tier=tier)
        wl.replay(eng, max_steps=4000, rid_prefix="warm-")
        eng.metrics = ServingMetrics()  # compile time stays off the clock
        eng.metrics.set_slo(**_SERVING_SLOS[name])
        eng.metrics.set_host_tier(tier is not None)
        out = wl.replay(eng, max_steps=4000, rid_prefix="run-")
        m = eng.metrics.summary()
        assert eng.decode_program_count() == 1, "serving decode retraced"
        arms[arm] = (eng, m, out)
    eng, m, out = arms["tiered"]
    assert eng.pool.host_tier.counters["restored_pages"] > 0, \
        "tiered arm never restored — pool no longer under pressure"
    m0 = arms["notier"][1]
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = out["steps"] * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    wstats = wl.stats()
    return {
        "metric": "llama_420m_serving_tiered_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mbu, 4),
        "extra": {"params": n_params, "workload": wstats,
                  "max_new_tokens": max_new_tokens,
                  "engine_steps": out["steps"],
                  "submitted": out["submitted"], "shed": out["shed"],
                  "cache_hit_rate": round(m["cache_hit_rate"], 4),
                  "cache_hit_rate_notier": round(m0["cache_hit_rate"], 4),
                  "tier_hbm_hit_rate": round(m["tier_hbm_hit_rate"], 4),
                  "tier_host_hit_rate": round(m["tier_host_hit_rate"], 4),
                  "tier_miss_rate": round(m["tier_miss_rate"], 4),
                  "spilled_pages": m["spilled_pages"],
                  "restored_pages": m["restored_pages"],
                  "spilled_bytes": m["spilled_bytes"],
                  "restored_bytes": m["restored_bytes"],
                  "host_pool_bytes": m["host_pool_bytes"],
                  "prefill_restored_tokens": m["prefill_restored_tokens"],
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_notier": round(m0["goodput_at_slo"], 4),
                  "tokens_per_s_notier": round(m0["tokens_per_s"], 1),
                  "slo": _SERVING_SLOS[name],
                  "retraces": eng.decode_program_count() - 1,
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


class _StreamRecorder:
    """Replay target that wraps an engine and keeps each request's
    emitted tokens — the tensor-parallel A/B asserts the two arms'
    streams bitwise identical, which ``Workload.replay``'s summary dict
    alone cannot show."""

    def __init__(self, eng):
        self.eng = eng
        self.scheduler = eng.scheduler     # replay's has_work probe
        self.tokens = {}

    def add_request(self, *args, **kw):
        return self.eng.add_request(*args, **kw)

    def step(self):
        events = self.eng.step()
        for ev in events:
            if ev.get("token") is not None:
                self.tokens.setdefault(ev["rid"], []).append(ev["token"])
        return events


def bench_llama_serving_disagg(peak, peak_kind, n_requests=10,
                               prompt_scale=10.0, trace_path=None):
    """Disaggregated prefill/decode serving A/B (SERVING.md
    "Disaggregated serving"): the seeded long-prompt Workload replayed
    at prompt_scale 1x and 10x, each scale served by a colocated
    2-replica fleet (both replicas interleave prefill chunks with
    decode rows) and by the same fleet with ``placement="disagg"`` (one
    prefill specialist, one decode specialist, KV handed off over the
    wire). Loopback transport steps replicas sequentially in-process,
    so each arm is timed on a VIRTUAL PARALLEL CLOCK: per router step
    the measured clock advances by the slowest replica's engine-step
    wall time — the latency a fleet of parallel machines pays. The A/B
    evidence the driver wants is itl_p99 for both arms at both scales:
    colocated inter-token gaps stretch with the 10x prompts (every
    decode step shares a program dispatch with someone's prefill
    chunk), disagg gaps track the decode-only step and stay flat
    (itl_p99_ratio_10x). Streams are asserted bitwise identical between
    the arms at each scale — the handoff relocates KV, it never changes
    the math — and both arms assert zero program retraces."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (FleetMetrics, FleetRouter,
                                    ServingEngine, ServingMetrics,
                                    long_prompt_workload)

    name = "llama_serving_disagg"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    weight_bytes = 2.0 * n_params
    tracer = _make_tracer(trace_path)

    def run_arm(scale, disagg):
        wl = long_prompt_workload(seed=0, n_requests=n_requests,
                                  prompt_scale=scale)
        engines = [ServingEngine(model, num_pages=512, page_size=16,
                                 max_slots=8, max_pages_per_slot=64,
                                 chunked=True, prefill_chunk=64,
                                 prefill_token_budget=128,
                                 tracer=tracer if disagg else None)
                   for _ in range(2)]
        # warm both replicas so the measured replay pays no compiles;
        # the disagg prefill specialist (replica 0) warms mixed only —
        # warming decode there would void the phase-split contract
        engines[0].warm_programs(decode=not disagg)
        engines[1].warm_programs()
        engines[1].add_request(np.arange(1, 9, dtype=np.int32), 2)
        engines[1].run_to_completion(max_steps=100)
        warm_steps = [e.stats()["steps"] for e in engines]
        # virtual parallel clock: real replicas are separate machines,
        # but the loopback wire steps them back-to-back in one process —
        # per router step, advance measured time by the SLOWEST replica
        # step, the wall time a parallel fleet would pay for that step
        vt = [0.0]
        durs: list = []
        for e in engines:
            def timed(_orig=e.step):
                t0 = time.perf_counter()
                ev = _orig()
                durs.append(time.perf_counter() - t0)
                return ev
            e.step = timed
        router = FleetRouter(
            engines, placement="disagg" if disagg else "affinity",
            tracer=tracer if disagg else None)
        router.metrics = ServingMetrics(clock=lambda: vt[0])
        router.metrics.set_slo(**_SERVING_SLOS[name])
        router.fleet_metrics = FleetMetrics()

        class _Rec:  # replay target: route submits, tick the clock
            def submit(self, *args, **kw):
                return router.submit(*args, **kw)

            def has_work(self):
                return router.has_work()

            def step(self):
                durs.clear()
                router.step()
                vt[0] += max(durs, default=0.0)

        res = wl.replay(_Rec(), max_steps=20000)
        outs = {rid: list(router.request(rid).tokens)
                for rid in res["rids"]}
        m = router.metrics.summary()
        fleet = router.fleet_metrics.summary()
        retraces = sum(max(0, n - 1) for e in engines
                       for n in e.step_program_counts().values())
        assert retraces == 0, "serving step program retraced"
        engine_steps = sum(e.stats()["steps"] - w
                           for e, w in zip(engines, warm_steps))
        return {"outs": outs, "m": m, "fleet": fleet,
                "router_steps": res["steps"], "shed": res["shed"],
                "engine_steps": engine_steps}

    arms = {}
    for scale in (1.0, float(prompt_scale)):
        for disagg in (False, True):
            arms[(scale, disagg)] = run_arm(scale, disagg)
        # the tentpole's determinism contract, priced into the headline:
        # the handoff arm's streams are bitwise the colocated arm's
        assert arms[(scale, True)]["outs"] == arms[(scale, False)]["outs"], \
            f"disagg arm diverged from colocated at {scale}x"

    hi = float(prompt_scale)
    dis, col = arms[(hi, True)], arms[(hi, False)]
    dis1, col1 = arms[(1.0, True)], arms[(1.0, False)]
    m, m0 = dis["m"], col["m"]
    fleet = dis["fleet"]

    def ratio(a, b):
        return round(a / max(b, 1e-9), 4)

    hbm_bw = _HBM_BW[peak_kind]
    # fleet-aggregate weights floor over the PARALLEL wall: both
    # replicas stream the shared weights concurrently, so this can
    # legitimately exceed a single chip's ratio
    wall = max(m["wall_s"], 1e-9)
    mbu = dis["engine_steps"] * weight_bytes / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_disagg_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": ratio(m["tokens_per_s"], m0["tokens_per_s"]),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "prompt_scale": hi, "replicas": 2,
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "ttft_p99_colocated": round(m0["ttft_p99_s"], 4),
                  # p50 spans can read 0.0: a short prompt prefills
                  # inside ONE router step and the virtual parallel
                  # clock only ticks between steps — the long-prompt
                  # tail lives in the p99 columns
                  "ttft_queue_p50": round(
                      m.get("ttft_queue_wait_p50_s", 0.0), 4),
                  "ttft_prefill_p50": round(
                      m.get("ttft_prefill_p50_s", 0.0), 4),
                  "ttft_prefill_p99": round(
                      m.get("ttft_prefill_p99_s", 0.0), 4),
                  "ttft_handoff_p50": round(
                      m.get("ttft_handoff_p50_s", 0.0), 4),
                  "ttft_handoff_p99": round(
                      m.get("ttft_handoff_p99_s", 0.0), 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "itl_p99_colocated": round(m0["itl_p99_s"], 5),
                  "itl_p99_1x": round(dis1["m"]["itl_p99_s"], 5),
                  "itl_p99_colocated_1x":
                      round(col1["m"]["itl_p99_s"], 5),
                  "itl_p99_ratio_10x":
                      ratio(m["itl_p99_s"], dis1["m"]["itl_p99_s"]),
                  "itl_p99_colocated_ratio_10x":
                      ratio(m0["itl_p99_s"], col1["m"]["itl_p99_s"]),
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_colocated":
                      round(m0["goodput_at_slo"], 4),
                  "handoff_prefills": fleet.get("handoff_prefills", 0),
                  "handoff_pulls": fleet.get("handoff_pulls", 0),
                  "handoff_bytes": fleet.get("handoff_bytes", 0),
                  "handoff_recomputes":
                      fleet.get("handoff_recomputes", 0),
                  "handoff_commits": fleet.get("handoff_commits", 0),
                  "rerolls": fleet.get("rerolls", 0),
                  "shed": dis["shed"] + col["shed"],
                  "router_steps": dis["router_steps"],
                  "engine_steps": dis["engine_steps"],
                  "slo": _SERVING_SLOS[name],
                  "retraces": 0,
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_tp(peak, peak_kind, n_requests=12,
                           max_new_tokens=48, trace_path=None):
    """Tensor-parallel serving A/B (SERVING.md "Tensor-parallel
    serving"): ONE seeded staggered Workload trace served by a tp=1
    engine and by a tp=2 engine whose two step programs each run as one
    shard_map over the mp mesh (KV pool sharded on the kv-head dim,
    Megatron column/row weight layout, one psum per block). The arms'
    per-request token streams are asserted BITWISE IDENTICAL — sharding
    relocates math, it never changes it — so every delta in the summary
    (tokens/s, goodput_at_slo, per-shard KV bytes) is attributable to
    the mesh alone. Each arm replays the trace twice on one engine:
    epoch 1 warms the two compiled programs, epoch 2 is measured.
    Needs >= 2 devices (TPU slice, or CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exported
    before the first jax import)."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (ServingEngine, ServingMetrics,
                                    make_workload)

    name = "llama_serving_tp"
    if jax.device_count() < 2:
        raise RuntimeError(
            "llama_serving_tp needs >= 2 devices for the tp=2 arm; on "
            "CPU export XLA_FLAGS=--xla_force_host_platform_device_count"
            "=8 before running bench.py (jax is already initialized by "
            "the time this config runs, so the flag cannot be set here)")
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis="mp", fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    wl = make_workload(seed=0, n_requests=n_requests, arrival="poisson",
                       rate=0.5, tenants=3, zipf_alpha=1.2,
                       system_len=(96, 160),
                       prompt_mix=((0.7, 16, 48), (0.3, 48, 96)),
                       max_new=(max_new_tokens, max_new_tokens),
                       vocab_size=cfg.vocab_size)
    tracer = _make_tracer(trace_path)
    arms = {}
    for arm, deg in (("tp1", 1), ("tp2", 2)):
        eng = ServingEngine(model, num_pages=64, page_size=16,
                            max_slots=4, tracer=tracer, tp=deg)
        rec = _StreamRecorder(eng)
        wl.replay(rec, max_steps=4000, rid_prefix="warm-")
        eng.metrics = ServingMetrics()  # compile time stays off the clock
        eng.metrics.set_slo(**_SERVING_SLOS[name])
        eng.metrics.set_tp(deg, eng.pool.kv_bytes_per_token_shard())
        out = wl.replay(rec, max_steps=4000, rid_prefix="run-")
        m = eng.metrics.summary()
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}, \
            f"tp={deg} step retraced"
        streams = {r: t for r, t in rec.tokens.items()
                   if r.startswith("run-")}
        arms[arm] = (eng, m, out, streams)
    assert arms["tp1"][3] == arms["tp2"][3], \
        "tp=2 streams diverged from tp=1 — TP must be bitwise"
    eng, m, out, _ = arms["tp2"]
    m0 = arms["tp1"][1]
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = out["steps"] * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_tp_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mbu, 4),
        "extra": {"params": n_params, "workload": wl.stats(),
                  "max_new_tokens": max_new_tokens,
                  "engine_steps": out["steps"],
                  "submitted": out["submitted"], "shed": out["shed"],
                  "tp_degree": 2,
                  "tp_shard_kv_bytes_per_token":
                      eng.pool.kv_bytes_per_token_shard(),
                  "kv_bytes_per_token": eng.pool.kv_bytes_per_token(),
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_tp1": round(m0["goodput_at_slo"], 4),
                  "tokens_per_s_tp1": round(m0["tokens_per_s"], 1),
                  "bitwise_parity": True,
                  "slo": _SERVING_SLOS[name],
                  "retraces": eng.decode_program_count() - 1,
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_pp(peak, peak_kind, n_requests=12,
                           max_new_tokens=48, trace_path=None):
    """Pipeline-parallel serving A/B (SERVING.md "Pipeline-parallel
    serving"): ONE seeded staggered Workload trace served by a tp=2
    engine and by a pp=2 x tp=2 engine that stages the decoder along
    the stacked-layer axis (embed + first half on stage 0, lm_head +
    last half on stage 1), carves the KV pool per stage, and hands
    activations between stages with one ppermute ring INSIDE each of
    the two compiled step programs. The arms' per-request token streams
    are asserted BITWISE IDENTICAL — staging relocates layers, it never
    changes the math — so every delta in the summary is attributable to
    the pipeline alone. On the loopback harness both stages of the one
    shard_map program run back-to-back in-process, so each arm is timed
    on the VIRTUAL PARALLEL CLOCK (PR 16 precedent): the measured clock
    advances by each engine step's wall time, compile time off the
    clock (epoch 1 warms, epoch 2 is measured). The headline pipeline
    evidence: per-chip KV bytes exactly 1/pp of the tp-only shard, and
    the microbatched mixed step's pipeline_bubble_frac
    ``(pp-1)/(waves+pp-1)`` strictly below the unwaved ``(pp-1)/pp``.
    Needs >= 4 devices (TPU slice, or CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exported
    before the first jax import)."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (ServingEngine, ServingMetrics,
                                    make_workload)

    name = "llama_serving_pp"
    if jax.device_count() < 4:
        raise RuntimeError(
            "llama_serving_pp needs >= 4 devices for the pp=2 x tp=2 "
            "arm; on CPU export XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8 before running bench.py (jax is already "
            "initialized by the time this config runs, so the flag "
            "cannot be set here)")
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis="mp", fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    wl = make_workload(seed=0, n_requests=n_requests, arrival="poisson",
                       rate=0.5, tenants=3, zipf_alpha=1.2,
                       system_len=(96, 160),
                       prompt_mix=((0.7, 16, 48), (0.3, 48, 96)),
                       max_new=(max_new_tokens, max_new_tokens),
                       vocab_size=cfg.vocab_size)
    tracer = _make_tracer(trace_path)
    arms = {}
    for arm, pp in (("tp2", 1), ("pp2", 2)):
        eng = ServingEngine(model, num_pages=64, page_size=16,
                            max_slots=4, tracer=tracer, tp=2, pp=pp)
        # virtual parallel clock: a real pp x tp slice runs the one
        # compiled step across 2 x pp chips at once, but the loopback
        # harness executes every fake device in one process — score the
        # metrics on accumulated engine-step wall time so both arms pay
        # exactly their step cost, nothing else
        vt = [0.0]

        def timed(_orig=eng.step):
            t0 = time.perf_counter()
            ev = _orig()
            vt[0] += time.perf_counter() - t0
            return ev

        eng.step = timed
        rec = _StreamRecorder(eng)
        wl.replay(rec, max_steps=4000, rid_prefix="warm-")
        vt[0] = 0.0                     # compile time stays off the clock
        eng.metrics = ServingMetrics(clock=lambda _vt=vt: _vt[0])
        eng.metrics.set_slo(**_SERVING_SLOS[name])
        eng.metrics.set_tp(2, eng.pool.kv_bytes_per_token_shard())
        eng.metrics.set_pp(eng.pp, eng._pp_waves,
                           eng.pipeline_bubble_frac())
        out = wl.replay(rec, max_steps=4000, rid_prefix="run-")
        m = eng.metrics.summary()
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}, \
            f"pp={pp} step retraced"
        streams = {r: t for r, t in rec.tokens.items()
                   if r.startswith("run-")}
        arms[arm] = (eng, m, out, streams)
    assert arms["tp2"][3] == arms["pp2"][3], \
        "pp=2 streams diverged from tp-only — staging must be bitwise"
    eng, m, out, _ = arms["pp2"]
    m0 = arms["tp2"][1]
    # the two headline pipeline claims, priced into the summary
    shard_pp = eng.pool.kv_bytes_per_token_shard()
    shard_tp = arms["tp2"][0].pool.kv_bytes_per_token_shard()
    assert shard_pp * eng.pp == shard_tp, \
        "per-chip KV bytes must be exactly 1/pp of the tp-only shard"
    bubble = eng.pipeline_bubble_frac()
    bubble_unwaved = eng.pipeline_bubble_frac(waves=1)
    assert bubble < bubble_unwaved, \
        "microbatched bubble fraction must beat the unwaved schedule"
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = out["steps"] * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_pp_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mbu, 4),
        "extra": {"params": n_params, "workload": wl.stats(),
                  "max_new_tokens": max_new_tokens,
                  "engine_steps": out["steps"],
                  "submitted": out["submitted"], "shed": out["shed"],
                  "pp_degree": eng.pp, "tp_degree": 2,
                  "pp_waves": eng._pp_waves,
                  "pipeline_bubble_frac": round(bubble, 4),
                  "pipeline_bubble_frac_unwaved":
                      round(bubble_unwaved, 4),
                  "pp_stage_layers":
                      cfg.num_hidden_layers // eng.pp,
                  "tp_shard_kv_bytes_per_token": shard_pp,
                  "tp_shard_kv_bytes_per_token_tponly": shard_tp,
                  "kv_bytes_per_token": eng.pool.kv_bytes_per_token(),
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_tponly":
                      round(m0["goodput_at_slo"], 4),
                  "tokens_per_s_tponly": round(m0["tokens_per_s"], 1),
                  "bitwise_parity": True,
                  "slo": _SERVING_SLOS[name],
                  "retraces": eng.decode_program_count() - 1,
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": True, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama8b_shape(peak, peak_kind, batch=1, seq=4096, layers=2):
    """North-star-SHAPE evidence (VERDICT r4 missing #1): ``layers``
    llama_3_8b-config decoder layers (hidden 4096, ffn 14336, GQA 32/8,
    models/llama.py llama_3_8b) + the fused hard-label CE head over the
    full 128256 vocab, fwd+bwd+AdamW at seq 4096 bf16 with per-layer
    remat, on ONE chip. MFU physics at 8B shapes differs from the 420M
    proxy (bigger matmuls, relatively costlier 128k-vocab softmax and
    GQA-8 attention); this config measures exactly those shapes. The
    embedding is tied so the 525M-param vocab matrix is stored once
    (fits HBM next to fp32 AdamW moments); FLOPs/token = 6*N + 12*L*s*h
    counts the head matmul through the tied matrix."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    cfg = LlamaConfig(vocab_size=128256, hidden_size=4096,
                      intermediate_size=14336, num_hidden_layers=layers,
                      num_attention_heads=32, num_key_value_heads=8,
                      max_position_embeddings=seq, rope_theta=500000.0,
                      tie_word_embeddings=True, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None, recompute=True)
    model = LlamaForCausalLM(cfg)
    n_params = model.num_params()
    opt = pt.optimizer.AdamW(learning_rate=1e-4, parameters=model)
    step = pt.jit.TrainStep(model, opt,
                            lambda logits, labels: model.loss(logits, labels))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    dt, spread, lossv = _time_windows(step, lambda: (ids, ids), iters=10)
    tokens_per_sec = batch * seq / dt
    flops_per_token = 6.0 * n_params \
        + 12.0 * layers * seq * cfg.hidden_size
    mfu = flops_per_token * tokens_per_sec / peak
    return {
        "metric": f"llama8b_shape_{layers}layer_seq{seq}_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu": round(mfu, 4), "step_ms": round(dt * 1000, 2),
                  "params": n_params, "loss": round(lossv, 4),
                  "batch": batch, "seq": seq, "layers": layers,
                  "hidden": cfg.hidden_size, "vocab": cfg.vocab_size,
                  "gqa": "32/8", "recompute": True, "tied": True,
                  "peak": peak_kind, "pipeline": False, "runs": _RUNS,
                  "iters": 10, "spread": round(spread, 4)},
    }


def bench_llama_serving_fairness(peak, peak_kind, n_requests=40,
                                 trace_path=None):
    """Overload-control A/B (SERVING.md "Overload control & tenant
    fairness"): the canonical hot-tenant flood — ``overload_workload``,
    where low-priority tenant 0 carries ~2/3 of a bursty trace and the
    cold tenants are the interactive SLO classes — replayed twice on
    the same model: FCFS (the legacy global queue: the flood buries
    every cold arrival behind the hot backlog) vs fair scheduling +
    the brownout ladder (weighted virtual-token-counter admission,
    budget-shrink/drafter-off/priority-shed degradation). The evidence
    the driver wants is the COLD tenants' worst p99 TTFT and aggregate
    ``goodput_at_slo`` for BOTH arms in the bench_summary cell —
    fairness bounds the former without moving the latter backwards.
    Streams finished in both arms are asserted token-exact (scheduling
    is invisible in the tokens) and both arms assert zero retraces:
    every brownout level is host-side scalar churn, never a shape."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (BrownoutConfig, ServingEngine,
                                    ServingMetrics, overload_workload)

    name = "llama_serving_fairness"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    wl = overload_workload(seed=0, n_requests=n_requests, rate=2.0,
                           zipf_alpha=1.6, vocab_size=cfg.vocab_size)
    tracer = _make_tracer(trace_path)
    arms = {}
    for arm in ("fcfs", "fair"):
        kw = {}
        if arm == "fair":
            kw = dict(fair_scheduling=True,
                      brownout=BrownoutConfig(high_queue=10, low_queue=4,
                                              dwell_steps=2))
        eng = ServingEngine(model, num_pages=256, page_size=16,
                            max_slots=8, max_pages_per_slot=16,
                            prefill_token_budget=128,
                            tracer=tracer if arm == "fair" else None,
                            **kw)
        wl.replay(eng, max_steps=4000, rid_prefix="warm-")
        eng.metrics = ServingMetrics()  # compile time stays off the clock
        eng.metrics.set_fair(arm == "fair")
        eng.metrics.set_brownout(arm == "fair")
        eng.metrics.set_slo(**_SERVING_SLOS[name])
        rec = _StreamRecorder(eng)
        out = wl.replay(rec, max_steps=4000, rid_prefix="run-")
        m = eng.metrics.summary()
        retraces = sum(n - 1 for n in eng.step_program_counts().values())
        assert retraces == 0, "serving step program retraced"
        arms[arm] = (eng, m, out, rec.tokens)
    eng, m, out, toks = arms["fair"]
    eng0, m0, out0, toks0 = arms["fcfs"]
    # the fairness contract, priced into the headline: a request
    # finished in BOTH arms decoded the identical stream — admission
    # order and brownout levels are scheduling, never semantics
    both = sorted(set(toks) & set(toks0))
    assert both, "no request finished in both arms"
    for rid in both:
        assert toks[rid] == toks0[rid], f"{rid} diverged across arms"

    def cold_p99(metrics):
        per = metrics.per_tenant()
        vals = [v["ttft_p99_s"] for t, v in per.items()
                if t != 0 and v["finished"] > 0]
        return max(vals) if vals else 0.0

    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = out["steps"] * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_fairness_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(m["tokens_per_s"]
                             / max(m0["tokens_per_s"], 1e-9), 4),
        "extra": {"params": n_params, "workload": wl.stats(),
                  "engine_steps": out["steps"],
                  "engine_steps_fcfs": out0["steps"],
                  "submitted": out["submitted"],
                  "tokens_per_s_fcfs": round(m0["tokens_per_s"], 1),
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "cold_ttft_p99": round(cold_p99(eng.metrics), 4),
                  "cold_ttft_p99_fcfs": round(cold_p99(eng0.metrics), 4),
                  "per_tenant": {t: {"finished": v["finished"],
                                     "ttft_p99_s": round(
                                         v["ttft_p99_s"], 4),
                                     "shed": v["shed"]}
                                 for t, v in
                                 eng.metrics.per_tenant().items()},
                  "shed": m["shed"],
                  "shed_by_priority": eng.metrics.shed_by_priority(),
                  "brownout_transitions": m["brownout_transitions"],
                  "brownout_level1_steps": m["brownout_level1_steps"],
                  "brownout_level2_steps": m["brownout_level2_steps"],
                  "brownout_level3_steps": m["brownout_level3_steps"],
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_fcfs": round(m0["goodput_at_slo"], 4),
                  "slo": _SERVING_SLOS[name],
                  "retraces": sum(
                      n - 1
                      for n in eng.step_program_counts().values()),
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


def bench_llama_serving_lora(peak, peak_kind, n_requests=24, n_adapters=32,
                             max_new_tokens=48, trace_path=None):
    """Multi-tenant LoRA serving A/B (SERVING.md "Multi-tenant LoRA
    serving"): one staggered-arrival ragged trace served three ways on
    identically-configured engines — no adapter pool at all ("base"),
    every request bound to ONE adapter ("single"), and every request
    drawing its adapter from a Zipf-popularity distribution over
    ``n_adapters`` tenants ("multi", the headline arm). The pool holds
    8 live slots against 32 registered adapters, so the multi arm pays
    real churn: misses page adapters in from host RAM, LRU evictions
    spill cold ones back, and the adapter-table value swaps every
    admission — while ``step_program_counts()`` must stay
    ``{"decode": 1, "mixed": 1}`` (asserted; the design contract).
    The bench_summary cell carries the adapter economics next to the
    usual serving SLO keys: adapter_hit_rate (Zipf should keep it
    high), lora_bytes_streamed (the HBM<->host bandwidth adapter churn
    cost), and multi_vs_single_ratio — the acceptance gate is multi
    tokens/s >= 0.8x the single-adapter arm."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine, ServingMetrics
    from paddle_tpu.serving.lora import LoRAAdapter

    name = "llama_serving_lora"
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=4096, dtype="bfloat16",
                      mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    rng = np.random.default_rng(0)
    lens = [int(x) for x in rng.integers(64, 256, n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    adapters = [LoRAAdapter.random(f"tenant-{i}", cfg, rank=8, seed=i)
                for i in range(n_adapters)]
    # Zipf tenant popularity (alpha 1.2, same shape the tiered bench's
    # Workload uses): a few hot adapters dominate, the tail forces
    # misses + evictions
    w = 1.0 / np.arange(1, n_adapters + 1) ** 1.2
    zipf_draw = rng.choice(n_adapters, size=n_requests, p=w / w.sum())
    # plant the coldest tenants at the tail: together with the hot head
    # draws the trace touches more distinct adapters than the pool has
    # slots, so the multi arm's eviction churn is deterministic
    n_cold = min(8, n_adapters - 1, n_requests // 2)
    zipf_draw[-n_cold:] = np.arange(n_adapters - n_cold, n_adapters)
    tracer = _make_tracer(trace_path)

    def run_arm(arm):
        lora = (None if arm == "base"
                else {"max_live": 9, "max_rank": 8,
                      "host_tier": 1 << 30})
        eng = ServingEngine(model, num_pages=512, page_size=16,
                            max_slots=8, max_pages_per_slot=32,
                            tracer=tracer, lora=lora)
        hexes = ([] if arm == "base"
                 else [eng.register_adapter(a) for a in adapters])
        per_req = {"base": [None] * n_requests,
                   "single": [hexes[0] if hexes else None] * n_requests,
                   "multi": [hexes[k] if hexes else None
                             for k in zipf_draw]}[arm]
        eng.warm_programs()
        eng.metrics = ServingMetrics()  # compile time stays off the clock
        eng.metrics.set_lora(eng.adapters is not None)
        eng.metrics.set_slo(**_SERVING_SLOS[name])
        added = 2
        for p, a in zip(prompts[:2], per_req[:2]):
            eng.add_request(p, max_new_tokens, adapter=a)
        steps = 0
        while eng.scheduler.has_work() or added < n_requests:
            eng.step()
            steps += 1
            if added < n_requests and steps % 4 == 0:
                eng.add_request(prompts[added], max_new_tokens,
                                adapter=per_req[added])
                added += 1
        m = eng.metrics.summary()
        counts = eng.step_program_counts()
        assert counts["decode"] == 1 and counts["mixed"] <= 1, \
            f"{arm} arm retraced: {counts}"
        return eng, m, steps

    arms = {arm: run_arm(arm) for arm in ("base", "single", "multi")}
    eng, m, steps = arms["multi"]
    m_base, m_single = arms["base"][1], arms["single"][1]
    lst = eng.adapters.stats()
    assert lst["adapter_evictions"] > 0, \
        "multi arm never evicted — pool no longer under adapter pressure"
    hbm_bw = _HBM_BW[peak_kind]
    wall = max(m["wall_s"], 1e-9)
    mbu = steps * 2.0 * n_params / wall / hbm_bw
    trace_out = _dump_trace(tracer, trace_path, name)
    return {
        "metric": "llama_420m_serving_lora_tokens_per_sec",
        "value": round(m["tokens_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mbu, 4),
        "extra": {"params": n_params, "n_requests": n_requests,
                  "n_adapters": n_adapters,
                  "max_new_tokens": max_new_tokens,
                  "prompt_lens": lens, "engine_steps": steps,
                  "adapter_hit_rate": round(lst["adapter_hit_rate"], 4),
                  "adapter_loads": lst["adapter_loads"],
                  "adapter_evictions": lst["adapter_evictions"],
                  "adapter_spills": lst["adapter_spills"],
                  "lora_bytes_streamed": lst["lora_bytes_streamed"],
                  "lora_bytes_per_slot": lst["bytes_per_slot"],
                  "tokens_per_s_base": round(m_base["tokens_per_s"], 1),
                  "tokens_per_s_single":
                      round(m_single["tokens_per_s"], 1),
                  "multi_vs_single_ratio":
                      round(m["tokens_per_s"]
                            / max(m_single["tokens_per_s"], 1e-9), 4),
                  "ttft_p50": round(m["ttft_p50_s"], 4),
                  "ttft_p99": round(m["ttft_p99_s"], 4),
                  "tpot": round(m["tpot_mean_s"], 5),
                  "itl_p99": round(m["itl_p99_s"], 5),
                  "preemptions": m["preemptions"],
                  "rejected": m["rejected"],
                  "goodput_at_slo": round(m["goodput_at_slo"], 4),
                  "goodput_at_slo_base":
                      round(m_base["goodput_at_slo"], 4),
                  "goodput_at_slo_single":
                      round(m_single["goodput_at_slo"], 4),
                  "slo": _SERVING_SLOS[name],
                  "retraces": sum(
                      max(n - 1, 0)
                      for n in eng.step_program_counts().values()),
                  "trace": trace_out,
                  "mbu_weights_only": round(mbu, 4),
                  "peak": peak_kind, "hbm_bw": hbm_bw,
                  "pipeline": False, "runs": _RUNS,
                  "spread": None},
    }


_CONFIGS = {
    "llama_420m": bench_llama,
    "resnet50": bench_resnet50,
    "bert_base": bench_bert,
    "qwen2_moe": bench_qwen2_moe,
    "lenet_mnist": bench_lenet,
    # round-5 additions to the driver artifact (VERDICT r4 next #1/#3/#6):
    "llama8b_shape": bench_llama8b_shape,
    "llama_decode": bench_llama_decode,
    "llama_longctx": bench_llama_longctx,
    # continuous-batching serving over the paged KV pool (SERVING.md)
    "llama_serving": bench_llama_serving,
    # shared-system-prompt serving: prefix-cache hit path (SERVING.md
    # "Prefix caching") — TTFT/hit-rate evidence for the cache
    "llama_serving_prefix": bench_llama_serving_prefix,
    # int8 quantized serving (SERVING.md "Quantized KV & weights"): the
    # same decode/serving workloads with int8 KV + int8 weight streaming;
    # MBU denominators are the *necessary* int8 bytes
    "llama_decode_int8": lambda peak, kind, **kw: bench_llama_decode(
        peak, kind, kv_int8=True, **kw),
    "llama_serving_int8": lambda peak, kind, **kw: bench_llama_serving(
        peak, kind, quantized=True, **kw),
    # 2-replica FleetRouter with a mid-run replica kill (SERVING.md
    # "Engine fleet & failover"): client-visible tokens/s with the
    # failover replay priced in, plus failovers/replays/shed evidence
    "llama_serving_fleet": bench_llama_serving_fleet,
    # bounded-replay failover A/B (RESILIENCE.md "Serving recovery
    # playbook"): the fleet kill run twice — no snapshots (full replay)
    # vs a shared SnapshotStore (restore KV, replay only the delta);
    # bitwise-identical client streams by assertion, replay-work +
    # goodput_at_slo evidence for both arms
    "llama_serving_failover": bench_llama_serving_failover,
    # clean-vs-lossy wire A/B (SERVING.md "Fleet transport &
    # membership"): loopback vs seeded chaos transport with a healed
    # mid-run partition and a lease ejection; bitwise-identical client
    # streams by assertion, failover/fencing/goodput evidence for both
    # arms
    "llama_serving_partition": bench_llama_serving_partition,
    # loopback-vs-socket wire A/B (SERVING.md "Multi-host serving"):
    # the same trace over the in-process wire and over real localhost
    # TCP framing; bitwise-identical client streams by assertion,
    # frame/byte volume + zero reconnects/lease churn + goodput for
    # both arms
    "llama_serving_multihost": bench_llama_serving_multihost,
    # chunked-prefill A/B (SERVING.md "Chunked prefill & mixed steps"):
    # whole-prompt vs chunk-streamed prefill on a long-prompt +
    # decode-heavy trace; itl_p99/goodput for both arms, token-exact
    "llama_serving_chunked": bench_llama_serving_chunked,
    # speculative decoding A/B (SERVING.md "Speculative decoding"):
    # n-gram draft verified through the mixed step vs plain decode
    # on the same shared-system-prompt trace; token-exact by assertion
    "llama_serving_spec": bench_llama_serving_spec,
    # host-RAM KV tiering A/B on a Poisson multi-tenant Workload
    # (SERVING.md "KV tiering & traffic harness"): spill-off vs spill-on
    # under forced pool pressure; goodput_at_slo + tier hit rates
    "llama_serving_tiered": bench_llama_serving_tiered,
    # overload-control A/B (SERVING.md "Overload control & tenant
    # fairness"): FCFS vs fair-scheduling + brownout ladder on the
    # canonical hot-tenant flood; cold-tenant p99 TTFT + goodput for
    # both arms, streams finished in both asserted token-exact
    "llama_serving_fairness": bench_llama_serving_fairness,
    # tensor-parallel serving A/B (SERVING.md "Tensor-parallel
    # serving"): tp=1 vs tp=2 on one seeded trace, streams asserted
    # bitwise identical; per-shard KV bytes + goodput for both arms.
    # Needs >= 2 devices (CPU: XLA_FLAGS=--xla_force_host_platform_
    # device_count=8 exported before launch)
    "llama_serving_tp": bench_llama_serving_tp,
    # pipeline-parallel serving A/B (SERVING.md "Pipeline-parallel
    # serving"): tp=2 vs pp=2 x tp=2 on one seeded trace, virtual
    # parallel clock, streams asserted bitwise identical; per-chip KV
    # bytes (exactly 1/pp), microbatched vs unwaved bubble fraction +
    # goodput for both arms. Needs >= 4 devices (CPU: XLA_FLAGS=
    # --xla_force_host_platform_device_count=8 exported before launch)
    "llama_serving_pp": bench_llama_serving_pp,
    # disaggregated prefill/decode A/B (SERVING.md "Disaggregated
    # serving"): colocated vs phase-specialized 2-replica fleet on the
    # long-prompt trace at 1x and 10x prompt length, virtual parallel
    # clock; itl_p99 flatness + handoff counters + goodput for both
    # arms, streams asserted bitwise identical per scale
    "llama_serving_disagg": bench_llama_serving_disagg,
    # multi-tenant LoRA A/B (SERVING.md "Multi-tenant LoRA serving"):
    # base-only vs single-adapter vs Zipf-popular 32-adapter arms on
    # one staggered trace; adapter hit rate + streamed bytes + the
    # multi/single throughput ratio (acceptance: >= 0.8), programs
    # pinned at {decode: 1, mixed: 1} through the churn
    "llama_serving_lora": bench_llama_serving_lora,
}

# configs whose bench_summary cell carries extra keys beyond
# {value, mfu, spread} — mirrored as nulls in --dry skeleton mode so the
# driver sees a stable schema either way
_SUMMARY_EXTRA_KEYS = {
    "llama_serving": ("ttft_p50", "ttft_p99", "tpot",
                      "rejected", "timed_out", "quarantined",
                      "goodput_at_slo", "retraces"),
    "llama_serving_prefix": ("ttft_p50", "ttft_p99", "tpot",
                             "cache_hit_rate", "prefix_hits",
                             "prefix_evictions",
                             "goodput_at_slo", "retraces"),
    "llama_decode_int8": ("bytes_ratio_vs_bf16",),
    "llama_serving_int8": ("ttft_p50", "ttft_p99", "tpot",
                           "rejected", "timed_out", "quarantined",
                           "goodput_at_slo", "retraces",
                           "kv_quant_err_bound", "bytes_ratio_vs_bf16"),
    "llama_serving_fleet": ("ttft_p50", "ttft_p99", "tpot",
                            "failovers", "replayed_tokens", "shed",
                            "replicas_ejected",
                            "goodput_at_slo", "retraces"),
    "llama_serving_failover": ("ttft_p50", "ttft_p99", "tpot",
                               "failovers",
                               "replayed_tokens", "replayed_tokens_full",
                               "snapshot_restores", "snapshot_fallbacks",
                               "recovery_restored_tokens",
                               "recovery_replayed_tokens",
                               "goodput_at_slo", "goodput_at_slo_full",
                               "retraces"),
    "llama_serving_partition": ("ttft_p50", "ttft_p99", "tpot",
                                "failovers", "failovers_clean",
                                "stale_epoch_discarded",
                                "lease_expirations",
                                "duplicates_suppressed",
                                "transport_dropped",
                                "goodput_at_slo", "goodput_at_slo_clean",
                                "retraces"),
    "llama_serving_multihost": ("ttft_p50", "ttft_p99", "tpot",
                                "frames_sent", "frames_recv",
                                "frame_bytes_sent", "frame_bytes_recv",
                                "socket_reconnects",
                                "lease_expirations",
                                "goodput_at_slo",
                                "goodput_at_slo_loopback",
                                "tokens_per_s_loopback",
                                "retraces"),
    "llama_serving_chunked": ("ttft_p50", "ttft_p99", "tpot",
                              "itl_p99", "itl_p99_baseline",
                              "itl_p99_ratio",
                              "goodput_at_slo",
                              "goodput_at_slo_baseline",
                              "chunk_tokens_total", "retraces"),
    "llama_serving_spec": ("ttft_p50", "ttft_p99", "tpot",
                           "accept_rate", "draft_hit_rate",
                           "speedup_vs_decode",
                           "goodput_at_slo", "retraces"),
    "llama_serving_tiered": ("ttft_p50", "ttft_p99", "tpot",
                             "cache_hit_rate", "tier_hbm_hit_rate",
                             "tier_host_hit_rate", "tier_miss_rate",
                             "spilled_pages", "restored_pages", "shed",
                             "goodput_at_slo", "goodput_at_slo_notier",
                             "retraces"),
    "llama_serving_fairness": ("ttft_p50", "ttft_p99", "tpot",
                               "cold_ttft_p99", "cold_ttft_p99_fcfs",
                               "shed", "brownout_transitions",
                               "goodput_at_slo", "goodput_at_slo_fcfs",
                               "retraces"),
    "llama_serving_tp": ("ttft_p50", "ttft_p99", "tpot",
                         "tp_degree", "tp_shard_kv_bytes_per_token",
                         "kv_bytes_per_token",
                         "tokens_per_s_tp1",
                         "goodput_at_slo", "goodput_at_slo_tp1",
                         "retraces"),
    "llama_serving_pp": ("ttft_p50", "ttft_p99", "tpot",
                         "pp_degree", "pp_waves",
                         "pipeline_bubble_frac",
                         "pipeline_bubble_frac_unwaved",
                         "tp_shard_kv_bytes_per_token",
                         "tp_shard_kv_bytes_per_token_tponly",
                         "kv_bytes_per_token",
                         "tokens_per_s_tponly",
                         "goodput_at_slo", "goodput_at_slo_tponly",
                         "retraces"),
    "llama_serving_disagg": ("ttft_p50", "ttft_p99",
                             "ttft_p99_colocated", "tpot",
                             "itl_p99", "itl_p99_colocated",
                             "itl_p99_ratio_10x",
                             "itl_p99_colocated_ratio_10x",
                             "handoff_pulls", "handoff_bytes",
                             "handoff_recomputes",
                             "goodput_at_slo",
                             "goodput_at_slo_colocated", "retraces"),
    "llama_serving_lora": ("ttft_p50", "ttft_p99", "tpot",
                           "n_adapters", "adapter_hit_rate",
                           "adapter_loads", "adapter_evictions",
                           "lora_bytes_streamed",
                           "tokens_per_s_base", "tokens_per_s_single",
                           "multi_vs_single_ratio",
                           "goodput_at_slo", "goodput_at_slo_base",
                           "retraces"),
}

# opt-in configs (not in the default driver run — kept out to bound its
# wall time; run by name)
_EXTRA_CONFIGS = {
    "llama_longctx_32k": lambda peak, kind: bench_llama_longctx(
        peak, kind, seq=32768),
    # A/B arm for the fused Pallas MoE dispatch (PERF.md): same model and
    # shapes as qwen2_moe, dispatch="fused"
    "qwen2_moe_fused": lambda peak, kind: bench_qwen2_moe(
        peak, kind, ep_dispatch="fused"),
}


def _summary_entry(result, name=None):
    """Compact per-config summary cell: {value, mfu, spread} plus any
    config-specific keys (_SUMMARY_EXTRA_KEYS — e.g. serving's
    ttft_p50/ttft_p99/tpot). ``mfu`` takes whichever efficiency ratio the
    config reports (mfu, mfu_active, decode's batch-8 MBU, or serving's
    weights-only MBU); null when the config failed."""
    ex = result.get("extra") or {}
    mfu = ex.get("mfu", ex.get("mfu_active"))
    if mfu is None:
        mfu = ((ex.get("batches") or {}).get(8) or {}).get("mbu")
    if mfu is None:
        mfu = ex.get("mbu_weights_only")
    entry = {"value": result.get("value"), "mfu": mfu,
             "spread": ex.get("spread")}
    for k in _SUMMARY_EXTRA_KEYS.get(name, ()):
        entry[k] = ex.get(k)
    return entry


def main():
    argv = list(sys.argv[1:])
    # --trace PATH: dump a Chrome trace (Perfetto-loadable) of each
    # serving config's engine run. PATH gets the config name spliced in
    # before the extension. Parsed (and removed) BEFORE the config-name
    # filter below — PATH itself does not start with "-".
    trace_path = None
    if "--trace" in argv:
        i = argv.index("--trace")
        if i + 1 >= len(argv):
            raise SystemExit("--trace requires a PATH argument")
        trace_path = argv[i + 1]
        del argv[i:i + 2]
    args = [a for a in argv if not a.startswith("-")]
    dry = "--dry" in argv
    all_configs = {**_CONFIGS, **_EXTRA_CONFIGS}
    unknown = [a for a in args if a not in all_configs]
    if unknown:
        raise SystemExit(f"unknown bench config(s) {unknown}; "
                         f"choose from {list(all_configs)}")
    names = args or list(_CONFIGS)
    summary = {}
    if dry:
        # parse/skeleton mode (CI smoke test): no jax import, no device
        # work — emit only the final summary line with every selected
        # config present, values null
        for name in names:
            summary[name] = {"value": None, "mfu": None, "spread": None,
                             **{k: None
                                for k in _SUMMARY_EXTRA_KEYS.get(name, ())}}
        print(json.dumps({"bench_summary": summary, "dry": True}),
              flush=True)
        return

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU, JAX found {dev.platform!r} "
            f"({dev.device_kind}); a CPU run gives no device number "
            f"(--dry prints the summary skeleton)")
    peak, peak_kind = _detect_peak(dev)
    enable_compile_cache()
    failed = []

    def _release_hbm():
        # release the finished config's HBM before the next one: the big
        # configs (llama8b_shape needs ~14 GB for fp32 AdamW moments) OOM
        # if earlier configs' params/opt-states/compiled executables
        # linger — locals die on return, but jit caches pin buffers until
        # cleared
        import gc
        gc.collect()
        try:
            jax.clear_caches()
        except Exception:
            pass
        gc.collect()

    for name in names:
        kwargs = ({"trace_path": trace_path}
                  if trace_path is not None and name in _SERVING_SLOS
                  else {})
        # only the exception's repr is kept: holding the exception object
        # would pin its traceback's frames, whose locals are the very
        # params/opt-state jax Arrays the next config needs freed
        err = None
        try:
            result = all_configs[name](peak, peak_kind, **kwargs)
            print(json.dumps(result), flush=True)
            summary[name] = _summary_entry(result, name)
        except Exception as e:
            err = repr(e)[:300]
        finally:
            # the except block's implicit `del e` ran before this, so
            # gc here can actually collect the frame cycle + buffers
            _release_hbm()
        if err is not None:  # one config failing must not kill the others
            failed.append(name)
            summary[name] = {"value": None, "mfu": None, "spread": None,
                             **{k: None
                                for k in _SUMMARY_EXTRA_KEYS.get(name, ())}}
            print(json.dumps({"metric": name, "value": None, "unit": "error",
                              "vs_baseline": 0.0,
                              "extra": {"error": err}}),
                  flush=True)
    # driver contract: LAST stdout line = one-object summary of ALL
    # selected configs (before the failure exit, so partial runs report)
    print(json.dumps({"bench_summary": summary}), flush=True)
    if failed:  # ...but the run must still report failure to the driver
        raise SystemExit(f"bench config(s) failed: {failed}")


if __name__ == "__main__":
    main()
