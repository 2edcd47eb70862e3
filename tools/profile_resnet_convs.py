"""Per-shape conv timing for ResNet-50 on the chip — the profile behind
PROFILE_resnet50.md. Times every distinct (input, weight, stride) conv in
resnet50 fwd+bwd in bf16 NCHW (the bench configuration) and reports each
shape's share of step time vs its FLOP share.

Run: python tools/profile_resnet_convs.py  (uses the real TPU)
"""
import sys
sys.path.insert(0, "/root/repo")
import time

import numpy as np
import jax
import jax.numpy as jnp

# (count, B,Cin,Hin, Cout, k, stride) — resnet50 conv inventory at 224 input
B = 128
SHAPES = [
    (1,  3, 224, 64, 7, 2),    # stem
    (1,  64, 56, 64, 1, 1),    # stage1 reduce (first block)
    (3,  64, 56, 64, 3, 1),    # stage1 3x3
    (3,  64, 56, 256, 1, 1),   # stage1 expand
    (2,  256, 56, 64, 1, 1),   # stage1 reduce (blocks 2-3)
    (1,  256, 56, 256, 1, 1),  # stage1 downsample proj
    (1,  256, 56, 128, 1, 1),  # stage2 reduce (first)
    (1,  128, 56, 128, 3, 2),  # stage2 3x3 stride2
    (3,  128, 28, 128, 3, 1),  # stage2 3x3
    (4,  128, 28, 512, 1, 1),  # stage2 expand
    (3,  512, 28, 128, 1, 1),  # stage2 reduce
    (1,  256, 56, 512, 1, 2),  # stage2 proj stride2
    (1,  512, 28, 256, 1, 1),  # stage3 reduce (first)
    (1,  256, 28, 256, 3, 2),  # stage3 3x3 stride2
    (5,  256, 14, 256, 3, 1),  # stage3 3x3
    (6,  256, 14, 1024, 1, 1), # stage3 expand
    (5,  1024, 14, 256, 1, 1), # stage3 reduce
    (1,  512, 28, 1024, 1, 2), # stage3 proj stride2
    (1,  1024, 14, 512, 1, 1), # stage4 reduce (first)
    (1,  512, 14, 512, 3, 2),  # stage4 3x3 stride2
    (2,  512, 7, 512, 3, 1),   # stage4 3x3
    (3,  512, 7, 2048, 1, 1),  # stage4 expand
    (2,  2048, 7, 512, 1, 1),  # stage4 reduce
    (1,  1024, 14, 2048, 1, 2),# stage4 proj stride2
]


def time_conv(cin, hin, cout, k, stride, iters=20, reps=3):
    """fwd+bwd of one conv, looped ITERS times INSIDE one XLA program
    (lax.scan with a carry data-dependency so iterations cannot be CSE'd) —
    a single conv is far shorter than one host dispatch, so an
    out-of-program timing loop would measure only the dispatch."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, cin, hin, hin)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((cout, cin, k, k)) * 0.05, jnp.bfloat16)
    pad = "SAME" if k > 1 else "VALID"

    def f(x, w):
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), pad,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return (y.astype(jnp.float32) ** 2).mean()

    grad = jax.grad(f, argnums=(0, 1))

    @jax.jit
    def many(x, w):
        def body(c, _):
            gx, gw = grad(x + c.astype(x.dtype), w)
            return gw.astype(jnp.float32).ravel()[0] * 1e-20, None
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return c

    float(np.asarray(many(x, w)))  # compile + warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(np.asarray(many(x, w)))
        best = min(best, (time.perf_counter() - t0) / iters)
    hout = hin // stride
    flops = 3 * 2 * B * hout * hout * cout * cin * k * k  # fwd+bwd ~3x
    return best, flops


def main():
    rows = []
    total_t = total_f = 0.0
    for cnt, cin, hin, cout, k, s in SHAPES:
        dt, fl = time_conv(cin, hin, cout, k, s)
        rows.append((cnt, cin, hin, cout, k, s, dt * cnt, fl * cnt,
                     fl / dt / 1e12))
        total_t += dt * cnt
        total_f += fl * cnt
    rows.sort(key=lambda r: -r[6])
    print(f"{'n':>2} {'cin':>5} {'h':>4} {'cout':>5} {'k':>2} {'s':>2} "
          f"{'ms(tot)':>8} {'%time':>6} {'%flop':>6} {'TF/s':>6}")
    for cnt, cin, hin, cout, k, s, t, f, tf in rows:
        print(f"{cnt:>2} {cin:>5} {hin:>4} {cout:>5} {k:>2} {s:>2} "
              f"{t*1000:>8.2f} {100*t/total_t:>6.1f} {100*f/total_f:>6.1f} "
              f"{tf:>6.1f}")
    print(f"\nconv total: {total_t*1000:.1f} ms, {total_f/1e9:.0f} GFLOP, "
          f"avg {total_f/total_t/1e12:.1f} TF/s "
          f"({100*total_f/total_t/197e12:.1f}% of v5e peak)")


if __name__ == "__main__":
    main()
