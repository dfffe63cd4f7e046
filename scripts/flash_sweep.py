"""Flash-attention block sweep / decomposition harness (real TPU).

Usage:
  python scripts/flash_sweep.py decompose     # fwd-only vs fwd+bwd timing
  python scripts/flash_sweep.py sweep         # interleaved block configs

Interleaved rounds with per-round min-of-k chained iterations; per-config
MEDIAN across rounds (keep the median, not the best window).  Overrides require jax.clear_caches() — the
block globals are trace-time only (see flash_attention.py note).
"""

import statistics
import sys
import time

sys.path.insert(0, __file__.rsplit("/scripts/", 1)[0])

import jax
import jax.numpy as jnp

from torchdistx_tpu.ops.pallas import flash_attention as fa

S, B, H, D = 16384, 1, 8, 128
PEAK = 197.0  # v5e bf16 TF/s


def make_inputs():
    key = jax.random.PRNGKey(0)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D),
                          dtype=jnp.bfloat16)
        for i in range(3)
    )


def time_chained(step, q, k, v, n=20, reps=3, grads=True):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        x, y, z = q, k, v
        for _ in range(n):
            if grads:
                gq, gk, gv = step(x, y, z)
                x, y, z = gq.astype(x.dtype), gk.astype(y.dtype), gv.astype(z.dtype)
            else:
                o = step(x, y, z)
                x = o.astype(x.dtype)
        float(x.astype(jnp.float32).sum())
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def build(kind):
    if kind == "fwd":
        f = jax.jit(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
        )
        return f, False
    f = jax.jit(
        jax.grad(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
    )
    return f, True


def decompose():
    q, k, v = make_inputs()
    for kind in ("fwd", "fwdbwd"):
        step, grads = build(kind)
        r = step(q, k, v)
        jax.block_until_ready(r)
        dt = time_chained(step, q, k, v, grads=grads)
        fwd_flops = 2 * 2 * B * H * S * S * D * 0.5
        tot = fwd_flops * (3.5 if grads else 1.0)
        print(f"{kind}: {dt*1e3:.2f} ms  mfu={tot/dt/1e12/PEAK:.4f}")


CONFIGS = [
    # (bwd_q, bwd_kv, fwd_q, fwd_kv)
    (1024, 1024, 1024, 1024),   # CURRENT defaults (r5, mask-free bodies)
    (512, 1024, 512, 2048),     # r4 tuned
    (512, 1024, 1024, 1024),
    (1024, 1024, 512, 2048),
    (256, 2048, 512, 2048),
    (1024, 2048, 1024, 2048),
]


def sweep(rounds=3):
    q, k, v = make_inputs()
    times = {c: [] for c in CONFIGS}
    for r in range(rounds):
        for c in CONFIGS:
            fa._BWD_BLOCK_Q, fa._BWD_BLOCK_KV = c[0], c[1]
            fa._FWD_BLOCK_Q, fa._FWD_BLOCK_KV = c[2], c[3]
            jax.clear_caches()
            step, grads = build("fwdbwd")
            rr = step(q, k, v)
            jax.block_until_ready(rr)
            dt = time_chained(step, q, k, v, n=10, reps=2)
            times[c].append(dt)
            print(f"round{r} {c}: {dt*1e3:.2f} ms", flush=True)
    print("--- medians")
    fwd_flops = 2 * 2 * B * H * S * S * D * 0.5
    for c in CONFIGS:
        med = statistics.median(times[c])
        print(f"{c}: {med*1e3:.2f} ms  mfu={3.5*fwd_flops/med/1e12/PEAK:.4f}")


if __name__ == "__main__":
    {"decompose": decompose, "sweep": sweep}[sys.argv[1]]()
