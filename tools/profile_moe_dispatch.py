"""A/B harness: fused Pallas grouped-GEMM MoE dispatch vs the packed
grouped path, on the chip — the measurement behind the round-6 addendum
in PROFILE_qwen2_moe.md.

Times the routed MoE path (gate + dispatch + expert FFNs + combine) at
the bench shapes: hidden 1024, moe_intermediate 704, 16 experts top-2
(capacity 1280 = 1.25x), batch 8 x seq 1024 (T = 8192 tokens), bf16
expert weights.

Protocol (PROFILE_qwen2_moe.md): fwd+bwd per iteration — `jax.vjp`
inside a `lax.scan` with a carry data-dependency, cotangent = output —
with DELTA timing, t(scan 40) minus t(scan 10) over 30, so per-sync
and program-entry fixed costs cancel. Like the other component
profiles, the functions close over weights (activation-gradient
backward, no weight-gradient GEMMs); the fused path's dW kernels are
exercised end-to-end by the full-step A/B instead:
`python bench.py qwen2_moe qwen2_moe_fused`.

Run: python tools/profile_moe_dispatch.py   (real TPU; on CPU it runs
the Pallas interpreter — logic check only, timings meaningless)
"""
import sys
sys.path.insert(0, "/root/repo")
import time

import numpy as np


def delta_time(fn, x, reps=3, n_long=40, n_short=10):
    """ms/iter via DELTA timing: (t(scan 40) - t(scan 10)) / 30."""
    import jax
    import jax.numpy as jnp

    def body(c, _):
        y, vjp = jax.vjp(fn, x + c.astype(x.dtype))
        (dx,) = vjp(y)
        return dx.astype(jnp.float32).ravel()[0] * 1e-20, None

    def scan_n(n):
        @jax.jit
        def prog():
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
            return c
        float(np.asarray(prog()))  # compile + warmup
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(np.asarray(prog()))
            best = min(best, time.perf_counter() - t0)
        return best

    return (scan_n(n_long) - scan_n(n_short)) / (n_long - n_short) * 1000


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.core.dtypes import set_default_dtype
    from paddle_tpu.distributed.moe import MoELayer, TopKGate
    from paddle_tpu.ops.pallas.moe_grouped_gemm import (
        fused_dispatch_applicable)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"WARNING: backend={backend} — Pallas interpreter, "
              f"timings are meaningless off-chip")

    smoke = "--smoke" in sys.argv[1:]  # tiny shapes, CPU logic check
    T, D, H, E = (512, 128, 96, 8) if smoke else (8192, 1024, 704, 16)
    n_long, n_short = (4, 1) if smoke else (40, 10)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.bfloat16)

    layers = {}
    for dispatch in ("grouped", "fused"):
        pt.seed(0)  # identical weights in both arms
        set_default_dtype("bfloat16")
        try:
            gate = TopKGate(D, E, top_k=2)  # gate weight stays fp32
            layers[dispatch] = MoELayer(D, num_experts=E, d_hidden=H,
                                        gate=gate, ep_axis=None,
                                        dispatch=dispatch)
        finally:
            set_default_dtype("float32")

    cap = layers["fused"].gate.capacity(T)
    ffn = layers["fused"].experts
    ok = fused_dispatch_applicable(T, D, ffn.w_in.shape[2], E, cap,
                                   x.dtype, ffn.activation, ffn.gated)
    print(f"shapes: T={T} D={D} H={H} E={E} cap={cap} bf16 "
          f"fused_applicable={ok}")
    assert ok, "fused kernel would fall back at bench shapes — fix the gate"

    # parity before timing: both arms, same weights, same routing
    outs = {k: np.asarray(m(x), np.float32) for k, m in layers.items()}
    md = float(np.max(np.abs(outs["fused"] - outs["grouped"])))
    print(f"fwd parity |fused - grouped|_max = {md:.3e}")

    results = {}
    for name, layer in layers.items():
        results[name] = delta_time(layer, x, reps=1 if smoke else 3,
                                   n_long=n_long, n_short=n_short)
        print(f"routed path [{name:7s}]: {results[name]:7.3f} ms/iter")

    speedup = results["grouped"] / results["fused"]
    print(f"\nfused/grouped step ratio: {1 / speedup:.3f} "
          f"({'WIN' if speedup > 1 else 'LOSS'} {abs(speedup - 1) * 100:.1f}%)")
    print("record the result in PROFILE_qwen2_moe.md (round-6 addendum) "
          "either way; full-step A/B incl. dW: "
          "python bench.py qwen2_moe qwen2_moe_fused")


if __name__ == "__main__":
    main()
