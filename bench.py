"""Benchmark: deferred_init → JAX materialization + train-step MFU on TPU.

The BASELINE workload family (BASELINE.md): construct a torch model under
deferred init (zero allocation), then materialize its parameters directly as
``jax.Array``s on the TPU.  The measured baseline is the workflow this
replaces — eager torch CPU init followed by host→device transfer of every
parameter (cast to bf16 on host, the standard TPU-training recipe).

Headline config: GPT-2-XL-shaped (~1.6B params, BASELINE config 3's scale) in
bf16 on one chip.  At this scale eager init+transfer is dominated by host RNG
and PCIe/host bandwidth while the deferred path generates parameters on-device
from a compact compiled program (compile time O(unique layer kinds) via the
grouped materializer — see materialize.py), so the ratio reflects the
framework's actual pitch.

Also measured (reported in details): the 124M config for round-over-round
continuity, fake-construction time, peak host RSS, and a training-step
throughput probe (tokens/s + MFU) of the flagship Llama stack with the Pallas
flash-attention kernel.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "details"}.
``vs_baseline`` > 1 means the deferred path beats eager-init-and-transfer.
"""

from __future__ import annotations

import json
import time

import torch
import torch.nn as nn


class Block(nn.Module):
    def __init__(self, dim: int, ffn: int):
        super().__init__()
        self.ln_1 = nn.LayerNorm(dim)
        self.attn_qkv = nn.Linear(dim, 3 * dim)
        self.attn_proj = nn.Linear(dim, dim)
        self.ln_2 = nn.LayerNorm(dim)
        self.mlp_fc = nn.Linear(dim, ffn)
        self.mlp_proj = nn.Linear(ffn, dim)


class GPT2(nn.Module):
    """GPT-2-shaped init workload (BASELINE config 3 family)."""

    def __init__(self, vocab=50257, dim=768, n_layer=12, seq=1024):
        super().__init__()
        self.wte = nn.Embedding(vocab, dim)
        self.wpe = nn.Embedding(seq, dim)
        self.h = nn.ModuleList([Block(dim, 4 * dim) for _ in range(n_layer)])
        self.ln_f = nn.LayerNorm(dim)
        self.lm_head = nn.Linear(dim, vocab, bias=False)


def GPT2Small():
    return GPT2()


def GPT2XL():
    return GPT2(vocab=50257, dim=1600, n_layer=48, seq=1024)


def _rss_mb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024


def _rss_now_mb() -> float:
    """CURRENT resident set (VmRSS), not the lifetime peak — usable for
    configs measured after another config's multi-GB eager baseline."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024
    return 0.0


# Peak dense bf16 TFLOP/s per chip by device_kind substring (public specs).
_PEAK_TFLOPS = [
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
]


def _peak_tflops(device_kind: str) -> float:
    kind = device_kind.lower()
    for sub, tf in _PEAK_TFLOPS:
        if sub in kind:
            return tf
    # A device that is not in the table is an error, not a default: an
    # MFU field quietly left out reads as "not a TPU problem".
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device_kind!r}; add it to "
        "_PEAK_TFLOPS with its source"
    )


def bench_materialize_ours(model_fn, *, dtype, rng_impl="rbg", report_rss=True):
    """OUR side of the materialize comparison: deferred + JAX materialize,
    then a warm re-materialization.

    RSS is reported as a CURRENT-VmRSS delta around the materialize (not
    ``ru_maxrss``): configs after the first would otherwise echo an
    earlier config's eager host allocation in the lifetime peak.
    """
    import jax

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.materialize import materialize_module_jax

    # Phase breakdown and cache/fastpath counts come from telemetry, not
    # bench-side bookkeeping: the bench reports what the system measured
    # about itself.  No sink needed — last_profile is the phase-span view
    # (assembled on every call, sinks off) and counters() reads the live
    # registry.
    import torchdistx_tpu.materialize as _mat

    c0 = telemetry.counters()

    rss_before = _rss_now_mb()
    t0 = time.perf_counter()
    model = deferred_init(model_fn)
    fake_s = time.perf_counter() - t0
    arrays = materialize_module_jax(model, dtype=dtype, rng_impl=rng_impl)
    jax.block_until_ready(list(arrays.values()))
    ours_s = time.perf_counter() - t0
    rss_ours = _rss_now_mb()
    del model, arrays

    c1 = telemetry.counters()
    phases = {
        k: round(v, 4)
        for k, v in _mat.last_profile.items()
        if k.endswith("_s")
    }
    counters_delta = {
        k: c1[k] - c0.get(k, 0)
        for k in (
            "materialize.exec_cache_hits",
            "materialize.fill_fastpath_hits",
        )
        if c1.get(k, 0) - c0.get(k, 0)
    }

    # Warm re-materialization of the same architecture (sweep/restart/
    # re-shard flows): the executable cache skips trace + compile, leaving
    # fake construction + replay execution.  Min of 3: the measurement is
    # a fraction of a second.
    warm_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        model = deferred_init(model_fn)
        arrays = materialize_module_jax(
            model, dtype=dtype, rng_impl=rng_impl
        )
        jax.block_until_ready(list(arrays.values()))
        warm_s = min(warm_s, time.perf_counter() - t0)
        del model, arrays

    out = {
        "ours_s": round(ours_s, 4),
        "ours_warm_s": round(warm_s, 4),
        "fake_construction_s": round(fake_s, 4),
        "phases": phases,
        "telemetry_counters": counters_delta,
    }
    if report_rss:
        out["rss_ours_mb"] = round(rss_ours, 1)
        out["rss_before_mb"] = round(rss_before, 1)
        out["rss_ours_growth_mb"] = round(rss_ours - rss_before, 1)
    return out


def bench_materialize_eager(model_fn, *, dtype, out):
    """EAGER baseline: torch init on host, cast, transfer every param.
    Fills ``eager_*`` and the ``vs_baseline*`` ratios into ``out``.

    The INIT component takes min-of-2 (torch's CPU init was measured
    swinging 10.9 ↔ 34 s for the same 1.6B model — pure host CPU noise),
    so the ratio uses the baseline's best case.  The TRANSFER runs
    exactly once.
    """
    import jax
    import numpy as np

    import ml_dtypes

    np_dtype = (
        ml_dtypes.bfloat16 if dtype == torch.bfloat16 else np.float32
    )
    eager_init_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        eager = model_fn()
        eager_init_s = min(eager_init_s, time.perf_counter() - t0)
    t0 = time.perf_counter()
    moved = [
        jax.device_put(p.detach().numpy().astype(np_dtype))
        for p in eager.parameters()
    ]
    jax.block_until_ready(moved)
    baseline_s = eager_init_s + (time.perf_counter() - t0)
    n_params = sum(p.numel() for p in eager.parameters())
    del eager, moved

    out.update(
        eager_init_transfer_s=round(baseline_s, 4),
        eager_init_only_s=round(eager_init_s, 4),
        vs_baseline=round(baseline_s / out["ours_s"], 3),
        vs_baseline_warm=round(baseline_s / out["ours_warm_s"], 3),
        params=n_params,
    )
    return out


def bench_cold_uncached():
    """First-ever-run materialization cost, honestly measured: a fresh
    process with BOTH the persistent XLA cache and the in-process executable
    cache disabled, backend pre-warmed so only the materialization is timed.

    The in-process ``ours_s`` numbers ride the persistent compilation cache
    (legitimate: restarts/sweeps are the common case) — this subprocess
    measurement is the ratchet's floor, so cache behavior can't silently
    degrade first-ever-run cost (VERDICT r2 weak #7).
    """
    import json as _json
    import os
    import subprocess
    import sys

    env = dict(
        os.environ, TDX_NO_COMPILATION_CACHE="1", TDX_NO_EXEC_CACHE="1"
    )
    # JAX reads this one itself; left set, the "uncached" run would hit.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = r"""
import json, time, torch, torch.nn as nn
import jax
if jax.devices()[0].platform != "tpu":
    raise SystemExit("cold probe: platform is not tpu")
from torchdistx_tpu.deferred_init import deferred_init
from torchdistx_tpu.materialize import materialize_module_jax
from bench import GPT2XL, GPT2Small
from torchdistx_tpu.models.resnet_torch import resnet50
deferred_init(nn.Linear, 8, 8)
jax.block_until_ready(jax.device_put(1.0))
jax.block_until_ready(jax.random.key(0, impl="rbg"))
out = {}
for label, fn, dt in [
    ("gpt2xl_bf16", GPT2XL, torch.bfloat16),
    ("gpt2small_f32", GPT2Small, torch.float32),
    ("resnet50_f32", resnet50, torch.float32),
]:
    m = deferred_init(fn)
    t0 = time.perf_counter()
    arrs = materialize_module_jax(m, dtype=dt, rng_impl="rbg")
    jax.block_until_ready(list(arrs.values()))
    out[label] = round(time.perf_counter() - t0, 3)
    del m, arrs
print(json.dumps(out))
"""
    # Best of 2 fresh subprocesses, one after the other (a chip belongs
    # to one process at a time, so main() runs this probe BEFORE the
    # parent first touches JAX; a child started from a parent that holds
    # the chip fails or hangs).
    # The WHOLE run with the smaller headline (XL) number wins — a
    # per-key min would stitch numbers from different processes together,
    # and the derived *_vs_baseline ratios would no longer describe any
    # run that actually happened (ADVICE round 5).
    headline = "gpt2xl_bf16"
    best = None
    samples = 0
    err = None
    for _ in range(2):
        try:
            r = subprocess.run(
                [sys.executable, "-c", code],
                env=env,
                capture_output=True,
                text=True,
                timeout=900,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except (OSError, subprocess.SubprocessError) as e:
            err = err or {"error": f"{type(e).__name__}: {e}"}
            continue
        lines = r.stdout.strip().splitlines()
        if r.returncode != 0 or not lines:
            err = err or {
                "error": f"subprocess exited {r.returncode}",
                "stderr_tail": r.stderr[-2000:],
            }
            continue
        try:
            got = _json.loads(lines[-1])
        except ValueError as e:
            err = err or {
                "error": f"unparseable probe output: {e}",
                "stdout_tail": r.stdout[-2000:],
            }
            continue
        samples += 1
        if best is None or got.get(headline, float("inf")) < best.get(
            headline, float("inf")
        ):
            best = got
    if best is not None:
        best["samples"] = samples
        if samples < 2 and err is not None:
            # One sample only — say so, the best-of-2 claim didn't apply.
            best["second_sample_error"] = err.get("error", "unknown")
        return best
    return err


def bench_train_step():
    """Train-step throughput of the flagship Llama stack on one chip.

    ~350M-param model, bf16, Pallas flash attention; reports tokens/s and
    MFU against the chip's public peak bf16 FLOP/s.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from torchdistx_tpu.models import llama
    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import make_mesh, MeshSpec

    cfg = llama.LlamaConfig(
        vocab_size=32000,
        dim=1024,
        n_layers=16,
        n_heads=16,
        n_kv_heads=16,
        ffn_dim=4096,
        max_seq_len=1024,
        # 350M at batch 8 fits HBM with all activations saved; remat would
        # re-run every block's forward in the backward (~1/3 more FLOPs)
        # for memory this config doesn't need.  Measured: 0.345 → 0.381 MFU.
        remat=False,
    )
    batch, seq = 8, 1024
    mesh = make_mesh(MeshSpec(fsdp=1))
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-3), attn_impl="pallas"
    )
    state = init_fn(jax.random.PRNGKey(0))
    n_params = sum(
        int(jnp.size(p)) for p in jax.tree.leaves(state.params)
    )
    tokens = jax.device_put(
        jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
        ),
        ts.batch_sharding(mesh),
    )
    batch_dict = {"tokens": tokens, "targets": tokens}

    # Warmup (compile) then timed steps.  Sync via host transfer of the
    # loss; the state dependency chain serializes all steps before it.
    for _ in range(2):
        state, metrics = step_fn(state, batch_dict)
    float(metrics["loss"])
    n_steps = 10
    # Min of 3 chained runs.
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = step_fn(state, batch_dict)
        float(metrics["loss"])
        dt = min(dt, time.perf_counter() - t0)

    tokens_per_s = n_steps * batch * seq / dt
    # fwd+bwd matmul FLOPs ≈ 6·N per token, plus attention
    # 12·B·S²·D per layer per step (QKᵀ + PV, fwd 4·B·S²·D, bwd ×2).
    flops_per_step = (
        6.0 * n_params * batch * seq
        + 12.0 * batch * seq * seq * cfg.dim * cfg.n_layers
    )
    flops_per_s = flops_per_step * n_steps / dt
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind)
    out = {
        "params": n_params,
        "tokens_per_s": round(tokens_per_s, 1),
        "step_time_s": round(dt / n_steps, 4),
        "tflops_per_s": round(flops_per_s / 1e12, 2),
        "device_kind": kind,
        "loss_finite": bool(jnp.isfinite(metrics["loss"])),
        "mfu": round(flops_per_s / (peak * 1e12), 4),
    }
    # Publish through the same gauges parallel/fit.py feeds, so a trace
    # or snapshot taken around the bench reads the train numbers from the
    # system's registry rather than from this probe's locals.
    from torchdistx_tpu import telemetry

    telemetry.gauge("train.steps_per_s").set(round(n_steps / dt, 4))
    telemetry.gauge("train.tokens_per_s").set(out["tokens_per_s"])
    telemetry.gauge("train.mfu").set(out["mfu"])
    return out


def bench_generate():
    """KV-cache decode throughput of the flagship stack on one chip.

    The serving-side number: batch-8 greedy decode (prefill 128, 256 new
    tokens) through the single-program prefill+scan generator
    (models/generate.py).  Decode is memory-bandwidth-bound; report
    decode tokens/s and the implied HBM utilization (params read once per
    step is the traffic floor).
    """
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu.models import llama
    from torchdistx_tpu.models.generate import generate
    from torchdistx_tpu.parallel.mesh import make_mesh, MeshSpec

    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=1024, n_layers=16, n_heads=16, n_kv_heads=16,
        ffn_dim=4096, max_seq_len=1024, remat=False,
    )
    batch, prompt_len, new = 8, 128, 256
    params = llama.init_sharded(
        jax.random.PRNGKey(0), cfg, make_mesh(MeshSpec(fsdp=1))
    )
    n_params = sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size
    )
    key = jax.random.PRNGKey(2)

    def one_pass(new_tokens, n_iters=8):
        # Iterations chain on device (each call's output tokens feed the
        # next prompt) with ONE host sync at the end — per-call syncs
        # would put the host round-trip inside every sample (same
        # discipline as the other probes).
        p = prompt
        t0 = time.perf_counter()
        for i in range(n_iters):
            out = generate(
                params, p, key, model=llama, cfg=cfg,
                max_new_tokens=new_tokens,
            )
            p = out[:, :prompt_len]
        int(p[0, 0])  # host sync
        return (time.perf_counter() - t0) / n_iters

    # Warmup/compile both lengths, syncing via host transfer like the
    # other probes.
    for n in (new // 2, new):
        out = generate(
            params, prompt, key, model=llama, cfg=cfg, max_new_tokens=n
        )
        int(out[0, 0])

    # Pure decode rate as the MARGINAL between two generation lengths —
    # the shared prefill (and its 128-token forward) cancels out of the
    # difference, so the number moves only when decode moves.  The two
    # lengths are measured in INTERLEAVED passes (min-of-3 each), so a
    # drift over the run lands on both sides of the difference.
    dt_half = float("inf")
    dt_full = float("inf")
    for _ in range(3):
        dt_half = min(dt_half, one_pass(new // 2))
        dt_full = min(dt_full, one_pass(new))
    out = {
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new,
        "e2e_tokens_per_s": round(batch * new / dt_full, 1),
        "sequences_per_s": round(batch / dt_full, 2),
    }
    if dt_full > dt_half:
        decode_step_s = (dt_full - dt_half) / (new - new // 2)
        out["decode_tokens_per_s"] = round(batch / decode_step_s, 1)
        # Per decode step every parameter is read once (bf16): HBM floor.
        out["param_read_gb_per_s"] = round(
            n_params * 2.0 / decode_step_s / 1e9, 1
        )
    else:
        # Drift swamped the marginal in every interleaved pass: flag it
        # rather than reporting an absurd clamped rate.
        out["decode_rate_error"] = "non-positive marginal (drift)"
    return out


def bench_serving():
    """Continuous-batching serving throughput of the flagship stack.

    The serving-path decode ratchet: the same 350M llama as
    ``bench_generate``, but behind the serving engine — 8 decode slots
    over a paged KV cache, mixed prompt/output lengths, Poisson-ish
    arrivals from a fixed seed.  Reports SUSTAINED decode tok/s
    (committed tokens / decode-dispatch time, slots kept full by
    continuous batching), TTFT p50/p95 (queue wait included), and peak
    block utilization — plus a **prefix-heavy phase**: 80% of requests
    share a 96-token system prompt, run against a cache-off and a
    cache-on engine on the SAME trace (``prefix_hit_rate``,
    mixed-traffic ``ttft_p95_s`` both ways, the cache's p95 speedup) —
    plus a **multi-tenant QoS phase**: a burst tenant's t=0 backlog vs
    a steady tenant's deadline-bearing higher-priority requests, FIFO
    and QoS engines paired on the SAME trace, reporting per-tenant
    TTFT p95, the steady tenant's deadline-hit rate both ways, and the
    preemption counts (``deadline_hit_improvement`` is the acceptance
    number — QoS must not lose to FIFO).
    Contrast with ``generate_llama_350m_decode``:
    there the whole batch finishes together and the cache is allocated
    at ``prompt+max_new`` per row; here slots recycle the moment a
    request's budget lands and pages free with them.

    Latency numbers come from the telemetry layer, not ad-hoc lists:
    TTFT/TPOT percentiles read back from the per-engine ``serve.*``
    histograms (via ``Engine.stats()``), and the per-tenant QoS numbers
    from :func:`scripts.trace_report.reconstruct` over the run's own
    event stream — the same reconstruction path a production trace or
    chaos soak goes through, so bench and post-mortem numbers can never
    drift apart.
    """
    import os
    import sys

    import jax
    import numpy as np

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.models import llama
    from torchdistx_tpu.parallel.mesh import make_mesh, MeshSpec
    from torchdistx_tpu.serving import (
        Engine,
        init_paged_cache,
        swap_in_pages,
        swap_out_pages,
    )

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts")
    )
    from trace_report import reconstruct

    from torchdistx_tpu.telemetry import ops as tdx_ops

    # Collect the run's own trace in memory: the reconstruction below
    # reads the SAME event stream a production TDX_TELEMETRY trace
    # carries (restored to the caller's settings at the end).
    prev_telemetry = telemetry.configure(collect=True, max_spans=65536)
    telemetry.drain()
    # Per-tick utilization attribution WITHOUT an HTTP listener: the
    # drive loop below samples serve.occupancy / serve.goodput each tick
    # for the utilization numbers (restored at the end).
    prev_attr = tdx_ops.enable_tick_attribution(True)

    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=1024, n_layers=16, n_heads=16, n_kv_heads=16,
        ffn_dim=4096, max_seq_len=1024, remat=False,
    )
    params = llama.init_sharded(
        jax.random.PRNGKey(0), cfg, make_mesh(MeshSpec(fsdp=1))
    )
    num_slots, block_size, max_model_len, chunk = 8, 32, 512, 16
    # 87.5% of dense capacity: paging has to work (requests queue when
    # pages run out), without starving the slots.
    num_blocks = 1 + int(num_slots * (max_model_len // block_size) * 7 / 8)

    def make_engine():
        return Engine(
            params, model=llama, cfg=cfg, num_slots=num_slots,
            block_size=block_size, num_blocks=num_blocks,
            max_model_len=max_model_len, decode_chunk=chunk,
            min_prefill_bucket=32,
        )

    rng = np.random.default_rng(0)
    n_req = 32
    plens = rng.integers(32, 192, size=n_req)
    outs = rng.integers(64, 256, size=n_req)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(p)).astype(np.int32)
        for p in plens
    ]
    # Poisson-ish arrivals: inter-arrival gaps in engine ticks.
    arrival = np.cumsum(rng.poisson(1.0, size=n_req))

    # Warm every compiled program (prefill per bucket + the decode chunk)
    # on a throwaway engine; the measured engine reuses the jit cache.
    warm = make_engine()
    wrng = np.random.default_rng(1)
    for p in (32, 64, 128, 192):  # covers every prefill bucket used below
        warm.submit(
            wrng.integers(0, cfg.vocab_size, size=p).astype(np.int32),
            max_new_tokens=4, key=0,
        )
    warm.drain()
    # Compile observatory baseline (docs/observability.md, "Perf
    # plane"): everything below reuses the warm jit cache, so ANY
    # decode-chunk compile from here on is a steady-state recompile —
    # the invariant the engine's perf model rests on, asserted at the
    # end of this bench.
    compiles_before = {
        k: v for k, v in telemetry.counters().items()
        if k.startswith("compile.")
    }

    def run_trace(eng, trace_prompts, trace_outs, trace_arrival):
        peak_util = 0.0
        # The per-tick attribution gauges (docs/observability.md, "Ops
        # plane"), sampled every tick: mean decode-batch occupancy,
        # mean goodput, and the time plane's host/device split over the
        # ticks that actually decoded.
        g_occ = telemetry.gauge("serve.occupancy", engine=eng.engine_id)
        g_good = telemetry.gauge("serve.goodput", engine=eng.engine_id)
        g_host = telemetry.gauge(
            "serve.host_overhead_frac", engine=eng.engine_id
        )
        occ_sum = good_sum = host_sum = 0.0
        decode_ticks = 0
        t0 = time.perf_counter()
        i, tick = 0, 0
        n = len(trace_prompts)
        while (
            i < n or len(eng.scheduler) or eng.stats()["running"]
            or eng.audit_backlog()
        ):
            while i < n and trace_arrival[i] <= tick:
                eng.submit(
                    trace_prompts[i], max_new_tokens=int(trace_outs[i]), key=i
                )
                i += 1
            eng.step()
            tick += 1
            peak_util = max(peak_util, eng.allocator.utilization())
            occ = g_occ.value or 0.0
            if occ > 0:
                decode_ticks += 1
                occ_sum += occ
                good_sum += g_good.value or 0.0
                host_sum += g_host.value or 0.0
        st = eng.stats()
        if decode_ticks:
            st["mean_decode_batch_occupancy"] = round(
                occ_sum / decode_ticks, 4
            )
            st["goodput_tokens_per_s"] = round(good_sum / decode_ticks, 1)
            st["host_overhead_frac"] = round(host_sum / decode_ticks, 4)
        return time.perf_counter() - t0, peak_util, st

    def tick_phase_rows(eng):
        """The time plane's per-tick phase breakdown for one engine
        (docs/observability.md, "Time plane"): per-phase count/total/
        p50/p95 from timeplane.phase_summaries — the one readback over
        the serve.tick_phase_s{engine=,phase=} histogram family."""
        from torchdistx_tpu.telemetry import timeplane

        return {
            phase: {
                "count": summ["count"],
                "total_s": round(summ["sum"], 4),
                "p50_s": round(summ["p50"], 6),
                "p95_s": round(summ["p95"], 6),
            }
            for phase, summ in timeplane.phase_summaries(
                eng.engine_id
            ).items()
        }

    telemetry.drain()  # warm-up records are not the measured trace
    eng = make_engine()
    wall, peak_util, st = run_trace(eng, prompts, outs, arrival)
    headline_phases = tick_phase_rows(eng)
    total_tokens = int(sum(outs))
    # Reconstruct the measured run's own event stream — bench numbers
    # ride the same per-request timeline path as a production trace.
    trace_summary = reconstruct(telemetry.drain()).summary()

    # Prefix-heavy phase (the production shape: ~80% of traffic behind
    # one system prompt): the SAME trace runs against a cache-off and a
    # cache-on engine — hit rate, TTFT p95, and sustained decode read
    # off each, so the cache's effect is a paired comparison on one
    # trace, not a cross-trace guess.
    prng = np.random.default_rng(2)
    system = prng.integers(0, cfg.vocab_size, size=96).astype(np.int32)
    p_prompts = []
    for _ in range(n_req):
        tail = prng.integers(
            0, cfg.vocab_size, size=int(prng.integers(8, 64))
        ).astype(np.int32)
        p_prompts.append(
            np.concatenate([system, tail]) if prng.random() < 0.8 else tail
        )
    p_outs = prng.integers(32, 128, size=n_req)
    p_arrival = np.cumsum(prng.poisson(1.0, size=n_req))
    prefix = {"system_prompt_tokens": 96, "shared_fraction": 0.8}
    for label, cache_on in (("cache_off", False), ("cache_on", True)):
        peng = Engine(
            params, model=llama, cfg=cfg, num_slots=num_slots,
            block_size=block_size, num_blocks=num_blocks,
            max_model_len=max_model_len, decode_chunk=chunk,
            min_prefill_bucket=32, prefix_cache=cache_on,
        )
        p_wall, p_peak, p_st = run_trace(peng, p_prompts, p_outs, p_arrival)
        row = {
            "wall_s": round(p_wall, 3),
            "ttft_p50_s": p_st.get("ttft_p50_s"),
            "ttft_p95_s": p_st.get("ttft_p95_s"),
            "sustained_decode_tokens_per_s": p_st.get("decode_tokens_per_s"),
            "peak_block_utilization": round(p_peak, 4),
            "mean_decode_batch_occupancy": p_st.get(
                "mean_decode_batch_occupancy"
            ),
            "goodput_tokens_per_s": p_st.get("goodput_tokens_per_s"),
            "host_overhead_frac": p_st.get("host_overhead_frac"),
        }
        if cache_on:
            row["prefix_hit_rate"] = round(p_st["prefix_hits"] / n_req, 3)
            row["prefix_hit_tokens"] = p_st["prefix_hit_tokens"]
            row["cow_copies"] = p_st["cow_copies"]
            row["prefix_evictions"] = p_st["prefix_evictions"]
        prefix[label] = row
    off_p95 = prefix["cache_off"].get("ttft_p95_s")
    on_p95 = prefix["cache_on"].get("ttft_p95_s")
    if off_p95 and on_p95:
        prefix["ttft_p95_speedup"] = round(off_p95 / on_p95, 3)

    # Multi-tenant QoS phase (ISSUE 8): a burst tenant dumping its whole
    # backlog at t=0 against a steady tenant submitting higher-priority,
    # deadline-bearing requests — the SAME trace against a FIFO engine
    # (tenant/priority inert) and a QoS engine (weighted fair queueing +
    # priority preemption), so per-tenant TTFT p95 and the steady
    # tenant's deadline-hit rate are a paired comparison.  The deadline
    # is calibrated from one solo steady-sized request on the warm
    # engine: generous for a promptly-served request, hopeless behind
    # the whole burst.
    mrng = np.random.default_rng(3)
    n_burst, n_steady = 24, 8
    b_prompts = [
        mrng.integers(
            0, cfg.vocab_size, size=int(mrng.integers(64, 161))
        ).astype(np.int32)
        for _ in range(n_burst)
    ]
    b_outs = mrng.integers(64, 129, size=n_burst)
    s_prompts = [
        mrng.integers(
            0, cfg.vocab_size, size=int(mrng.integers(32, 65))
        ).astype(np.int32)
        for _ in range(n_steady)
    ]
    s_outs = mrng.integers(32, 65, size=n_steady)
    s_arrival = np.arange(n_steady) * 2  # engine ticks between arrivals

    # Warm the preemption programs against the MEASURED pool shape: the
    # swap gather/scatter jits specialize on (pool shape, page bucket),
    # so drive them directly on a throwaway pool of the same shape, one
    # round per power-of-two bucket a victim's private page count can
    # hit.  A drill engine with a smaller pool would compile for the
    # wrong shape and the measured QoS run would pay first-preemption
    # compile stalls out of its deadlines.
    pool = init_paged_cache(llama, cfg, num_blocks, block_size)
    bucket = 1
    while bucket <= max_model_len // block_size:
        pages = list(range(1, bucket + 1))
        host = swap_out_pages(pool, pages)
        pool = swap_in_pages(pool, host, pages)
        bucket *= 2
    del pool
    # A drop-and-replay resume re-prefills prompt + generated-so-far in
    # one chunk — up to ~288 tokens here, the 512 bucket, which the
    # 32..192 warm prompts above never reach.
    warm2 = make_engine()
    warm2.submit(
        mrng.integers(0, cfg.vocab_size, size=320).astype(np.int32),
        max_new_tokens=4, key=0,
    )
    warm2.drain()

    cal = make_engine()
    t0 = time.perf_counter()
    cal.submit(s_prompts[0], max_new_tokens=int(s_outs[0]), key=0).result()
    unit_s = time.perf_counter() - t0
    deadline_s = max(1.0, 8.0 * unit_s)

    def run_multi_tenant(eng):
        telemetry.drain()
        burst_handles = [
            eng.submit(
                p, max_new_tokens=int(o), key=100 + i, tenant="burst",
                priority=0,
            )
            for i, (p, o) in enumerate(zip(b_prompts, b_outs))
        ]
        steady_handles = []
        i, tick = 0, 0
        while i < n_steady or len(eng.scheduler) or eng.stats()["running"]:
            while i < n_steady and s_arrival[i] <= tick:
                steady_handles.append(
                    eng.submit(
                        s_prompts[i], max_new_tokens=int(s_outs[i]),
                        key=200 + i, tenant="steady", priority=1,
                        deadline_s=deadline_s,
                    )
                )
                i += 1
            eng.step()
            tick += 1
        # Per-tenant numbers from the run's reconstructed timelines (the
        # tenant rides each req.submitted event) — not ad-hoc handle
        # lists: the trace is the single source of latency truth.
        rep = reconstruct(telemetry.drain())
        ttfts = {"burst": [], "steady": []}
        n_seen = {"burst": 0, "steady": 0}
        n_done = {"burst": 0, "steady": 0}
        for tl in rep.requests.values():
            sub = next(
                e for e in tl._sorted() if e["name"] == "req.submitted"
            )
            tenant = (sub.get("attrs") or {}).get("tenant", "default")
            n_seen[tenant] += 1
            if tl.outcome == "finished":
                n_done[tenant] += 1
            if tl.ttft_s is not None:
                ttfts[tenant].append(tl.ttft_s)
        out = {}
        for tenant in ("burst", "steady"):
            row = {"n": n_seen[tenant], "completed": n_done[tenant]}
            if ttfts[tenant]:
                row["ttft_p95_s"] = round(
                    float(np.percentile(ttfts[tenant], 95)), 4
                )
            out[tenant] = row
        out["steady"]["deadline_hit_rate"] = round(
            n_done["steady"] / n_steady, 3
        )
        out["trace_complete"] = not rep.problems()
        st = eng.stats()
        out["preemptions_swap"] = st.get("preemptions_swap", 0)
        out["preemptions_replay"] = st.get("preemptions_replay", 0)
        return out

    multi = {
        "n_burst": n_burst,
        "n_steady": n_steady,
        "steady_deadline_s": round(deadline_s, 3),
        "fifo": run_multi_tenant(make_engine()),
        "qos": run_multi_tenant(
            Engine(
                params, model=llama, cfg=cfg, num_slots=num_slots,
                block_size=block_size, num_blocks=num_blocks,
                max_model_len=max_model_len, decode_chunk=chunk,
                min_prefill_bucket=32, scheduler="qos",
                tenant_weights={"steady": 4.0, "burst": 1.0},
            )
        ),
    }
    # The acceptance number: QoS must not hit FEWER steady deadlines
    # than FIFO on the same trace (it should hit strictly more under
    # any real burst).
    multi["deadline_hit_improvement"] = round(
        multi["qos"]["steady"]["deadline_hit_rate"]
        - multi["fifo"]["steady"]["deadline_hit_rate"],
        3,
    )

    # Audit-overhead phase (ISSUE 14): the SAME headline trace against
    # an engine shadow-auditing at 100% sampling — every completed
    # request re-executes once through the same compiled programs when
    # the queue is quiet.  Paired sustained tok/s plus the wall-clock
    # multiple; the sustained ratio is the acceptance number
    # bench_gate's tolerance band holds (auditing reuses the warm
    # programs, so it also rides the decode-recompile assert below).
    telemetry.drain()
    aeng = Engine(
        params, model=llama, cfg=cfg, num_slots=num_slots,
        block_size=block_size, num_blocks=num_blocks,
        max_model_len=max_model_len, decode_chunk=chunk,
        min_prefill_bucket=32, audit_sample=1.0,
    )
    a_wall, _a_peak, a_st = run_trace(aeng, prompts, outs, arrival)
    assert a_st.get("audit_divergences", 0) == 0, (
        "shadow audit diverged during the bench — determinism broke"
    )
    audit_row = {
        "audit_sample": 1.0,
        "wall_s": round(a_wall, 3),
        "sustained_decode_tokens_per_s": a_st.get("decode_tokens_per_s"),
        "audit_checked": a_st.get("audit_checked"),
        "audit_divergences": a_st.get("audit_divergences"),
        "wall_overhead_x": round(a_wall / wall, 3) if wall else None,
    }
    if st.get("decode_tokens_per_s") and a_st.get("decode_tokens_per_s"):
        audit_row["sustained_ratio"] = round(
            a_st["decode_tokens_per_s"] / st["decode_tokens_per_s"], 3
        )

    # Perf plane (ISSUE 12): per-program compile counts across the
    # measured phases, the steady-state decode-recompile invariant, and
    # the HBM ledger's component attribution.  The decode chunk was
    # compiled by the warm engine; the measured engines share its jit
    # cache, so a nonzero delta here means shape churn leaked into the
    # decode path — exactly what the recompile-storm detector guards
    # live, asserted hard at bench time.
    compile_counts = {
        k: v - compiles_before.get(k, 0)
        for k, v in telemetry.counters().items()
        if k.startswith("compile.count") and v - compiles_before.get(k, 0)
    }
    decode_recompiles = compile_counts.get(
        "compile.count{program=decode_chunk}", 0
    )
    assert decode_recompiles == 0, (
        f"steady-state decode chunk recompiled {decode_recompiles}x "
        "during the measured serving phases (shape leak)"
    )
    hbm_rows = {
        k: v for k, v in telemetry.gauges().items()
        if k.startswith("mem.hbm_bytes")
    }

    tdx_ops.enable_tick_attribution(prev_attr)
    telemetry.configure(**prev_telemetry)
    return {
        "n_requests": n_req,
        "num_slots": num_slots,
        "block_size": block_size,
        "num_blocks": num_blocks,
        "decode_chunk": chunk,
        "total_new_tokens": total_tokens,
        "wall_s": round(wall, 3),
        "e2e_tokens_per_s": round(total_tokens / wall, 1),
        # TTFT/TPOT percentiles read back from the per-engine telemetry
        # histograms (stats() is a view over them since ISSUE 9).
        "sustained_decode_tokens_per_s": st.get("decode_tokens_per_s"),
        "ttft_p50_s": st.get("ttft_p50_s"),
        "ttft_p95_s": st.get("ttft_p95_s"),
        "tpot_p50_s": st.get("tpot_p50_s"),
        "tpot_p95_s": st.get("tpot_p95_s"),
        "peak_block_utilization": round(peak_util, 4),
        # Per-tick utilization attribution (ISSUE 10): how full the
        # decode batch ran, and committed decode tokens per tick-second
        # — the serving analogue of train-side MFU.
        "mean_decode_batch_occupancy": st.get("mean_decode_batch_occupancy"),
        "goodput_tokens_per_s": st.get("goodput_tokens_per_s"),
        # Time plane (ISSUE 15): host vs device split of the tick loop
        # (mean over decoding ticks — near 1 means host-bound, a faster
        # kernel buys nothing) and the per-phase tick decomposition.
        "host_overhead_frac": st.get("host_overhead_frac"),
        "tick_phase_s": headline_phases,
        # The run's own reconstructed timelines (scripts/trace_report.py):
        # every request must reconstruct complete, and the phase totals
        # say where the wall time went.
        "trace": {
            "n_requests": trace_summary["n_requests"],
            "complete": trace_summary["complete"],
            "phase_totals_s": trace_summary["phase_totals_s"],
            "problems": len(trace_summary["problems"]),
        },
        "prefix_heavy": prefix,
        "multi_tenant": multi,
        # Audit plane (ISSUE 14): auditor overhead, sustained tok/s
        # audit on vs off on the same trace.
        "audit": audit_row,
        # Perf plane: what compiled (per program) during the measured
        # phases, the asserted steady-state invariant, and where the
        # device bytes sit (the HBM ledger's component attribution).
        "compile_counts": compile_counts,
        "decode_recompiles_steady": decode_recompiles,
        "hbm_bytes": hbm_rows,
    }


def bench_fleet_failover():
    """Fleet resilience probe: failover + hot-swap cost under load.

    Mixed traffic over a 2-engine :class:`~torchdistx_tpu.fleet
    .FleetRouter`; one engine is killed (device failure + close) at 50%
    of the pulls and a zero-downtime hot swap retires the survivor at
    75%.  Reports completed / failed-typed counts (both failure counts
    must be 0 — the probe injects no deadlines or cancels, so every
    request must complete somewhere), the p95 pull latency of
    failed-over vs clean requests and their delta (the failover tax:
    backoff + re-submit + token-identical replay — measured from
    sequential pulls, so queue position is in both groups' baseline),
    and the hot-swap request-drop count, which must be 0.
    """
    import jax
    import numpy as np

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.fleet import FleetRouter, hot_swap
    from torchdistx_tpu.models import llama
    from torchdistx_tpu.serving import Engine, RequestError

    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=512, n_layers=8, n_heads=8, n_kv_heads=8,
        ffn_dim=2048, max_seq_len=512, remat=False,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    def make_engine():
        return Engine(
            params, model=llama, cfg=cfg, num_slots=4, block_size=16,
            max_model_len=256, decode_chunk=8, min_prefill_bucket=32,
            handle_preemption=False,
        )

    # Warm the compiled programs on a throwaway engine (shared jit cache).
    warm = make_engine()
    wrng = np.random.default_rng(1)
    for p in (32, 64, 128):
        warm.submit(
            wrng.integers(0, cfg.vocab_size, size=p).astype(np.int32),
            max_new_tokens=4, key=0,
        )
    warm.drain()
    warm.close()

    rng = np.random.default_rng(0)
    n_req = 32
    eng_a, eng_b = make_engine(), make_engine()
    router = FleetRouter([eng_a, eng_b], version="v1", max_hops=4)
    failovers_before = telemetry.counter("fleet.failovers").value
    handles = []
    for i in range(n_req):
        plen = int(rng.integers(16, 97))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        mnt = int(rng.integers(32, 97))
        handles.append(router.submit(prompt, max_new_tokens=mnt, key=i))

    eng_c = {"eng": None}
    swap_s = None
    lat_clean, lat_failover = [], []
    n_done = n_failed = 0
    for idx, h in enumerate(handles):
        if idx == n_req // 2:
            for leaf in jax.tree.leaves(eng_a._cache):
                leaf.delete()
            eng_a.close()
            router.poll()
        if idx == (3 * n_req) // 4:
            eng_c["eng"] = make_engine()
            t0 = time.perf_counter()
            hot_swap(router, lambda: eng_c["eng"], version="v2")
            swap_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        try:
            h.result()
            n_done += 1
            (lat_failover if h.hops else lat_clean).append(
                time.perf_counter() - t0
            )
        except RequestError:
            n_failed += 1

    out = {
        "n_requests": n_req,
        "completed": n_done,
        "failed_typed": n_failed,  # must be 0: no deadlines/cancels here
        "hot_swap_dropped": n_failed,  # the acceptance number (must be 0)
        "hot_swap_s": round(swap_s, 3) if swap_s is not None else None,
        "failovers": telemetry.counter("fleet.failovers").value
        - failovers_before,
    }
    if lat_clean:
        out["clean_pull_p95_s"] = round(
            float(np.percentile(lat_clean, 95)), 4
        )
    if lat_failover:
        out["failover_pull_p95_s"] = round(
            float(np.percentile(lat_failover, 95)), 4
        )
    if lat_clean and lat_failover:
        out["failover_added_latency_p95_s"] = round(
            float(np.percentile(lat_failover, 95))
            - float(np.percentile(lat_clean, 95)),
            4,
        )
    # The direct measurement (the fleet.failover_added_s histogram times
    # failure→re-placement per hop, backoff included) alongside the
    # derived pull-latency delta above.
    h = telemetry.histogram("fleet.failover_added_s")
    if h.count:
        out["failover_added_p95_s_hist"] = round(h.percentile(95), 4)
    return out


def bench_migration():
    """Stream-migration probe: what the warm hand-off costs vs the cold
    replay it replaces, and what prefill/decode disaggregation buys the
    decode tier's tail latency.

    Part 1 — identical decoding streams over a 2-engine fleet, two
    arms: (a) every stream is warm-migrated engine→engine mid-decode
    (``router.migrate_stream``: pages shipped, zero recomputed tokens);
    (b) the source engine is killed instead and the streams take the
    cold key-pinned replay.  Reports the per-stream hand-off wall time
    and each arm's consumer-visible p95 pull latency — the
    migration-vs-replay tax docs/fleet.md's failure matrix argues
    about.

    Part 2 — decode interference: p95/max inter-token gap of a chatty
    stream on the decode tier while a 192-token prompt lands, (a)
    prefilled on the SAME engine (fused baseline: the prefill rides the
    decode tick loop) vs (b) prefilled on a ``role="prefill"`` peer and
    warm-migrated in for its decode phase (disaggregated).  Only the
    chat pulls are timed in both arms — per-tier latency, not
    whole-host throughput (one host runs both engines here).
    """
    import jax
    import numpy as np

    from torchdistx_tpu.fleet import FleetRouter
    from torchdistx_tpu.models import llama
    from torchdistx_tpu.serving import Engine, RequestError

    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=512, n_layers=8, n_heads=8, n_kv_heads=8,
        ffn_dim=2048, max_seq_len=512, remat=False,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    def make_engine(role="mixed"):
        return Engine(
            params, model=llama, cfg=cfg, num_slots=4, block_size=16,
            max_model_len=256, decode_chunk=8, min_prefill_bucket=32,
            handle_preemption=False, role=role,
        )

    warm = make_engine()
    wrng = np.random.default_rng(1)
    for p in (32, 64, 128, 192):
        warm.submit(
            wrng.integers(0, cfg.vocab_size, size=p).astype(np.int32),
            max_new_tokens=4, key=0,
        )
    warm.drain()
    warm.close()

    rng = np.random.default_rng(0)
    n_req = 4  # one per slot: the whole set decodes (and moves) at once
    prompts = [
        rng.integers(
            0, cfg.vocab_size, size=int(rng.integers(16, 97))
        ).astype(np.int32)
        for _ in range(n_req)
    ]
    mnts = [int(rng.integers(32, 97)) for _ in range(n_req)]

    def run_arm(kill):
        eng_a, eng_b = make_engine(), make_engine()
        router = FleetRouter([eng_a, eng_b], version="v1", max_hops=4)
        rid_a = next(
            rid for rid, rep in router._replicas.items()
            if rep.engine is eng_a
        )
        eng_b.detector.observe_tick(50.0)  # pin routing to A
        handles = []
        for i, (p, mnt) in enumerate(zip(prompts, mnts)):
            handles.append(router.submit(p, max_new_tokens=mnt, key=i))
            eng_b.detector.observe_tick(50.0)
        for _ in range(10_000):
            if (
                not len(eng_a.scheduler)
                and eng_a._n_running()
                and eng_a._n_running() == eng_a._n_decoding()
            ):
                break
            eng_a.step()
        hand_off = []
        if kill:
            for leaf in jax.tree.leaves(eng_a._cache):
                leaf.delete()
            eng_a.close()
            router.poll()
        else:
            for slot in list(eng_a.migratable_slots()):
                t0 = time.perf_counter()
                if router.migrate_stream(rid_a, slot):
                    hand_off.append(time.perf_counter() - t0)
        lats, n_done = [], 0
        for h in handles:
            t0 = time.perf_counter()
            try:
                h.result()
                n_done += 1
            except RequestError:
                pass
            lats.append(time.perf_counter() - t0)
        router.close()
        return n_done, lats, hand_off

    n_mig_done, mig_lats, hand_off = run_arm(kill=False)
    n_cold_done, cold_lats, _ = run_arm(kill=True)

    out = {
        "n_streams": n_req,
        # Both arms must complete everything — warm or cold, no stream
        # is ever lost.
        "migrated_completed": n_mig_done,
        "cold_replay_completed": n_cold_done,
        "migrated_pull_p95_s": round(
            float(np.percentile(mig_lats, 95)), 4
        ),
        "cold_replay_pull_p95_s": round(
            float(np.percentile(cold_lats, 95)), 4
        ),
        "migration_saved_p95_s": round(
            float(np.percentile(cold_lats, 95))
            - float(np.percentile(mig_lats, 95)),
            4,
        ),
    }
    if hand_off:
        out["migration_handoff_p95_s"] = round(
            float(np.percentile(hand_off, 95)), 4
        )

    # ---- Part 2: decode-tier tail while a long prompt lands ----
    chat_prompt = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
    long_prompt = rng.integers(0, cfg.vocab_size, size=192).astype(np.int32)
    CHAT_NEW, LONG_AT = 48, 8

    def chat_gaps(pull_iter, on_token):
        gaps, last = [], time.perf_counter()
        for i, _tok in enumerate(pull_iter):
            gaps.append(time.perf_counter() - last)
            on_token(i)
            last = time.perf_counter()  # driver work stays untimed
        return gaps

    # (a) fused: the long prefill rides the chat stream's engine.
    eng = make_engine()
    chat = eng.submit(chat_prompt, max_new_tokens=CHAT_NEW, key=100)
    pending = {}

    def fused_on_token(i):
        if i == LONG_AT:
            pending["h"] = eng.submit(
                long_prompt, max_new_tokens=8, key=101
            )

    fused = chat_gaps(chat.tokens(), fused_on_token)
    if "h" in pending:
        while not pending["h"].done:
            eng.step()
    eng.close()

    # (b) disaggregated: the long prompt prefills on the prefill peer
    # and warm-migrates in for its decode phase.
    eng_p, eng_d = make_engine("prefill"), make_engine("decode")
    router = FleetRouter(
        [eng_p, eng_d], version="v1", max_hops=4, long_prompt_tokens=128,
    )
    chat = router.submit(chat_prompt, max_new_tokens=CHAT_NEW, key=100)
    state = {}

    def disagg_on_token(i):
        if i == LONG_AT:
            state["h"] = router.submit(
                long_prompt, max_new_tokens=8, key=101
            )
        elif "h" in state and not state["h"].done:
            eng_p.step()  # the prefill tier does its own work
            router.rebalance()  # decode-phase streams ship over

    disagg = chat_gaps(chat.tokens(), disagg_on_token)
    if "h" in state:
        try:
            state["h"].result()
        except RequestError:
            pass
    router.close()

    # gaps[0] is the chat TTFT (queue + its own prefill) — TPOT starts
    # at the second token in both arms.
    out["fused_chat_tpot_p95_ms"] = round(
        float(np.percentile(fused[1:], 95)) * 1e3, 2
    )
    out["disagg_chat_tpot_p95_ms"] = round(
        float(np.percentile(disagg[1:], 95)) * 1e3, 2
    )
    out["disagg_tpot_saved_p95_ms"] = round(
        out["fused_chat_tpot_p95_ms"] - out["disagg_chat_tpot_p95_ms"], 2
    )
    out["fused_chat_tpot_max_ms"] = round(max(fused[1:]) * 1e3, 2)
    out["disagg_chat_tpot_max_ms"] = round(max(disagg[1:]) * 1e3, 2)
    return out


def bench_autoscale():
    """Elastic fleet probe: what the observe→act loop buys in a flash
    crowd.

    Two arms over identical traffic (same seed, prompts, and token
    budgets): a FIXED single-engine fleet, then the same fleet with the
    signal-driven :class:`~torchdistx_tpu.fleet.Autoscaler` attached.  A
    10× flash crowd with a microsecond-deadline subset lights the SLO
    burn; the probe reports the autonomous time-to-recover (burn edge →
    recovery edge, from the autoscaler's own burn-event log), the peak
    replica count the loop reached, ramp TTFT p95 for both arms and
    their ratio, and the dropped count, which must be 0 — deadline
    misses are typed, anything else the elastic fleet must absorb.
    Scale-in back to one replica is part of the measurement: the probe
    fails the arm if the fleet does not land back at min.
    """
    import jax
    import numpy as np

    from torchdistx_tpu.fleet import AutoscaleConfig, Autoscaler, FleetRouter
    from torchdistx_tpu.models import llama
    from torchdistx_tpu.serving import (
        DeadlineExceeded,
        Engine,
        RequestCancelled,
        RequestError,
    )
    from torchdistx_tpu.telemetry import ops as tdx_ops

    cfg = llama.llama_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    def make_engine():
        return Engine(
            params, model=llama, cfg=cfg, num_slots=4, block_size=8,
            num_blocks=33, max_model_len=64, decode_chunk=4,
            drain_deadline_s=120.0, handle_preemption=False,
        )

    # Warm the compiled programs (shared jit cache across both arms).
    warm = make_engine()
    warm.submit(
        np.arange(8, dtype=np.int32) % cfg.vocab_size,
        max_new_tokens=4, key=0,
    )
    warm.drain()
    warm.close()

    n_crowd = 30

    def arm(autoscale):
        rng = np.random.default_rng(7)
        router = FleetRouter(
            [make_engine()], version="v1", max_hops=4,
            ops_port=0, ops_config=tdx_ops.OpsConfig(
                watchdog=False,
                slo=tdx_ops.SLOConfig(
                    slo=0.9, fast_window_s=2.0, slow_window_s=8.0,
                    burn_threshold=2.0, min_samples=4,
                ),
            ),
        )
        scaler = None
        if autoscale:
            scaler = Autoscaler(
                router, make_engine, version="v1",
                config=AutoscaleConfig(
                    min_replicas=1, max_replicas=3, fast_ticks=2,
                    slope_window=4, slope_high=3.0, slow_ticks=6,
                    scale_out_cooldown=4, scale_in_cooldown=6,
                    queue_low_per_replica=1.0,
                ),
            )

        handles, doomed, t_submit = [], set(), {}
        for i in range(n_crowd):
            plen = int(rng.integers(3, 14))
            prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(
                np.int32
            )
            d = None
            if rng.random() < 0.3:
                d = 1e-6  # typed misses that light the burn
            h = router.submit(
                prompt, max_new_tokens=int(rng.choice((4, 8, 12))),
                key=i, deadline_s=d,
            )
            handles.append(h)
            if d is not None:
                doomed.add(id(h))
            t_submit[id(h)] = time.perf_counter()

        ttfts, dropped, typed, done = [], 0, 0, 0
        peak = 1
        gens = [(h, h.tokens(), True) for h in handles]
        pulls = 0
        while gens:
            nxt = []
            for h, g, first in gens:
                try:
                    next(g)
                    if first and id(h) not in doomed:
                        ttfts.append(
                            time.perf_counter() - t_submit[id(h)]
                        )
                    nxt.append((h, g, False))
                except StopIteration:
                    done += 1
                except RequestError as e:
                    if isinstance(e, (DeadlineExceeded, RequestCancelled)):
                        typed += 1
                    else:
                        dropped += 1
                pulls += 1
                if scaler is not None and pulls % 8 == 0:
                    scaler.tick()
                    peak = max(peak, len(router.replicas()))
            gens = nxt

        out = {
            "completed": done,
            "deadline_typed": typed,
            "dropped": dropped,  # the acceptance number (must be 0)
            "ramp_ttft_p95_s": round(
                float(np.percentile(ttfts, 95)), 4
            ) if ttfts else None,
        }
        if scaler is not None:
            # Recovery: trickle good traffic until the burn clears.
            t0 = time.perf_counter()
            k = 10_000
            while scaler.recoveries < 1:
                if time.perf_counter() - t0 > 60.0:
                    out["recover_timeout"] = True
                    break
                trio = [
                    router.submit(
                        rng.integers(0, cfg.vocab_size, size=6).astype(
                            np.int32
                        ),
                        max_new_tokens=4, key=k + j,
                    )
                    for j in range(3)
                ]
                k += 3
                for h in trio:
                    for _ in h.tokens():
                        pass
                scaler.tick()
                time.sleep(0.2)
            edges = {}
            for t, tenant, burning in scaler.burn_events:
                edges.setdefault(burning, t)
            if True in edges and False in edges:
                out["time_to_recover_s"] = round(
                    edges[False] - edges[True], 3
                )
            # Quiet down: the loop must land back at min replicas.
            t0 = time.perf_counter()
            while (
                len(router.replicas()) > scaler.config.min_replicas
                and time.perf_counter() - t0 < 120.0
            ):
                scaler.tick()
                router.step()
                time.sleep(0.02)
            out["landed_at_min"] = (
                len(router.replicas()) == scaler.config.min_replicas
            )
            out["peak_replicas"] = peak
            out["scale_outs"] = scaler.scale_outs
            out["scale_ins"] = scaler.scale_ins
            scaler.close()
        router.close()
        return out

    fixed = arm(autoscale=False)
    auto = arm(autoscale=True)
    out = {
        "n_requests": n_crowd,
        "fixed": fixed,
        "autoscaled": auto,
        "dropped": fixed["dropped"] + auto["dropped"],  # must be 0
        "time_to_recover_s": auto.get("time_to_recover_s"),
        "peak_replicas": auto.get("peak_replicas"),
        "ramp_ttft_p95_s": auto.get("ramp_ttft_p95_s"),
    }
    if fixed.get("ramp_ttft_p95_s") and auto.get("ramp_ttft_p95_s"):
        out["ttft_p95_vs_fixed"] = round(
            auto["ramp_ttft_p95_s"] / fixed["ramp_ttft_p95_s"], 3
        )
    return out


def bench_models():
    """Model-plane probe: many models on one engine's page pool.

    One engine serves its own weights plus three deferred-init pool
    models (same geometry, different seeds) with ``max_resident=2`` —
    every cold demand past the budget thrashes the LRU weight eviction,
    so the probe prices exactly what the model plane trades: a
    materialize stall on first (or re-warmed) demand against near-zero
    HBM for cold models.  Reported: cold TTFT per model (includes the
    stall), warm TTFT p95 over a mixed four-model wave, the materialize
    stall p95 from the pool's own clock, eviction count, the decode
    recompile delta across models (must be 0 — same-geometry models
    share the one compiled decode chunk), and the n=4 parallel-sampling
    page amplification vs a solo request (prompt pages are shared via
    the fork donor; only divergence CoW-copies).
    """
    import jax
    import numpy as np

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.models import llama
    from torchdistx_tpu.serving import Engine, ModelPool

    cfg = llama.llama_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    def seeded(seed):
        def materialize():
            return llama.init_params(jax.random.PRNGKey(seed), cfg)
        return materialize

    pool = ModelPool(max_resident=2)
    for i, tag in enumerate(("m1", "m2", "m3"), start=1):
        pool.register(
            tag, model=llama, cfg=cfg, materialize=seeded(i),
            model_version=f"{tag}@v1",
        )
    eng = Engine(
        params, model=llama, cfg=cfg, num_slots=8, block_size=8,
        num_blocks=81, max_model_len=64, decode_chunk=4,
        handle_preemption=False, temperature=1.0, top_k=40,
        model_pool=pool,
    )
    rng = np.random.default_rng(3)

    def prompt(plen):
        return rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)

    def ttft(h):
        t0 = time.perf_counter()
        gen = h.tokens()
        next(gen)
        dt = time.perf_counter() - t0
        for _ in gen:
            pass
        return dt

    try:
        # Cold pass: first demand per tag pays materialize + compile.
        # The default model's weights are resident, so its cold TTFT is
        # the compile-only baseline the stall reads against.
        cold = {}
        for tag in (None, "m1", "m2", "m3"):
            cold[tag or "default"] = ttft(
                eng.submit(prompt(8), max_new_tokens=4, key=0, model=tag)
            )

        c0 = {
            k: v
            for k, v in telemetry.snapshot()["counters"].items()
            if "compile.count" in k and "decode" in k
        }

        # Warm wave: mixed four-model traffic.  m3 displaced one of
        # m1/m2 during the cold pass, so round-robin demand here keeps
        # re-warming evicted weights — warm p95 includes those stalls.
        warm = []
        tags = (None, "m1", "m2", "m3")
        for i in range(24):
            warm.append(ttft(eng.submit(
                prompt(int(rng.integers(4, 16))),
                max_new_tokens=int(rng.choice((4, 8))),
                key=100 + i, model=tags[i % 4],
            )))

        c1 = {
            k: v
            for k, v in telemetry.snapshot()["counters"].items()
            if "compile.count" in k and "decode" in k
        }
        decode_recompiles = sum(c1.values()) - sum(c0.values())

        # Fork amplification: n=4 over a 4-page prompt vs one solo.
        solo_h = eng.submit(prompt(32), max_new_tokens=8, key=7)
        solo_peak = 0
        while not solo_h.done:
            eng.step()
            solo_peak = max(solo_peak, eng.allocator.num_in_use)
        fork_h = eng.submit(prompt(32), max_new_tokens=8, key=7, n=4)
        fork_peak = 0
        while not all(s.done for s in fork_h.siblings):
            eng.step()
            fork_peak = max(fork_peak, eng.allocator.num_in_use)
        for s in fork_h.siblings:
            s.result()

        stats = pool.stats()
        out = {
            "n_models": 1 + stats["n_registered"],
            "cold_ttft_s": {k: round(v, 4) for k, v in cold.items()},
            "warm_ttft_p95_s": round(float(np.percentile(warm, 95)), 4),
            "materialize_p95_s": stats["materialize_p95_s"],
            "evictions": sum(
                m["evictions"] for m in stats["models"].values()
            ),
            "decode_recompiles": decode_recompiles,  # must be 0
            "fork_n4_peak_pages": fork_peak,
            "solo_peak_pages": solo_peak,
            "fork_page_amplification_vs_4x": round(
                fork_peak / (4 * solo_peak), 3
            ) if solo_peak else None,
        }
        eng.drain()
        return out
    finally:
        eng.close()


def bench_flash_attention(s=16384, b=1, h=8, d=128):
    """Long-context flash attention fwd+bwd at S=16k on one chip.

    The kernel streams KV through VMEM scratch (O(bq·d + bkv·d) VMEM at any
    S); this probe is the perf ratchet for the long-context regime.  Sync is
    via host transfer.
    """
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu.ops.pallas.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (b, s, h, d),
                          dtype=jnp.bfloat16)
        for i in range(3)
    )

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    # All three grads, so neither backward kernel is dead-code-eliminated
    # out of the timed program.
    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    gq, gk, gv = step(q, k, v)
    float(gq.astype(jnp.float32).sum())
    # Iterations chain on device (grads feed back into the inputs) with ONE
    # host sync at the end: per-iteration syncs would put a host
    # round-trip inside every sample.  Min of 3 runs.
    n = 20
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        x, y, z = q, k, v
        for _ in range(n):
            gq, gk, gv = step(x, y, z)
            x = gq.astype(x.dtype)
            y = gk.astype(y.dtype)
            z = gv.astype(z.dtype)
        float(x.astype(jnp.float32).sum())
        dt = min(dt, (time.perf_counter() - t0) / n)
    # Causal fwd QK^T+PV = 2·2·b·h·s²·d·½; bwd ≈ 2.5× fwd (dq,dk,dv + p
    # recompute).
    flops = 3.5 * 2.0 * b * h * s * s * d
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind)
    return {
        "seq_len": s,
        "fwd_bwd_ms": round(dt * 1e3, 2),
        "tflops_per_s": round(flops / dt / 1e12, 2),
        "attn_mfu": round(flops / dt / (peak * 1e12), 4),
    }


def _phase(fn):
    """Run one probe; a failure is recorded, not raised, so the later
    probes still run — main() turns any recorded error into a non-zero
    exit."""
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 — report, don't sink the bench
        return {"error": f"{type(e).__name__}: {e}"}


def main() -> int:
    import sys

    # The cold probe's children each need the chip, and a chip belongs to
    # one process at a time: they run BEFORE this process first touches
    # JAX (nothing above this line initializes a backend).
    cold = bench_cold_uncached()

    import jax
    import torch.nn as nn

    from torchdistx_tpu import telemetry

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # A timing from XLA's CPU backend says nothing about this system.
        print(
            f"bench.py: platform is {dev.platform!r}, not 'tpu'; refusing "
            "to run",
            file=sys.stderr,
        )
        return 2
    _peak_tflops(dev.device_kind)  # unknown device: fail now, not per probe
    jax.block_until_ready(jax.device_put(1.0))  # backend warm-up

    # Dispatch warm-up: the first op recorded under deferred init triggers
    # torch's lazy imports (dynamo/distributed/sympy, ~1.5s).  That is
    # torch's one-time process cost, not this framework's per-op record
    # cost; warm it so fake_construction_s measures the latter.
    from torchdistx_tpu.deferred_init import deferred_init

    deferred_init(nn.Linear, 8, 8)

    from torchdistx_tpu.models.resnet_torch import resnet50

    # Each config's OURS and EAGER run ADJACENTLY, so both sides of a
    # ratio see the same machine state, smallest config first.
    resnet = bench_materialize_ours(resnet50, dtype=torch.float32)
    bench_materialize_eager(resnet50, dtype=torch.float32, out=resnet)
    small = bench_materialize_ours(GPT2Small, dtype=torch.float32)
    bench_materialize_eager(GPT2Small, dtype=torch.float32, out=small)
    xl = bench_materialize_ours(GPT2XL, dtype=torch.bfloat16)
    bench_materialize_eager(GPT2XL, dtype=torch.bfloat16, out=xl)
    train = _phase(bench_train_step)
    flash16k = _phase(bench_flash_attention)
    gen = _phase(bench_generate)
    serving = _phase(bench_serving)
    # The serving ratchet reads directly against the solo-generate row it
    # shares hardware (and a model config) with.
    if "error" not in serving and gen.get("e2e_tokens_per_s"):
        sus = serving.get("sustained_decode_tokens_per_s")
        if sus:
            serving["vs_generate_e2e"] = round(
                sus / gen["e2e_tokens_per_s"], 3
            )
    fleet = _phase(bench_fleet_failover)
    autoscale = _phase(bench_autoscale)
    migration = _phase(bench_migration)
    model_plane = _phase(bench_models)
    # Honest cold ratios: first-ever-run (fresh process, all caches off)
    # against the same eager baselines measured above.
    if "error" not in cold:
        for label, eager_s in (
            ("gpt2xl_bf16", xl["eager_init_transfer_s"]),
            ("gpt2small_f32", small["eager_init_transfer_s"]),
            ("resnet50_f32", resnet["eager_init_transfer_s"]),
        ):
            if label in cold:
                cold[f"{label}_vs_baseline"] = round(
                    eager_s / cold[label], 3
                )

    print(
        json.dumps(
            {
                "metric": "deferred_init_materialize_gpt2xl_bf16_1chip",
                "value": xl["ours_s"],
                "unit": "s",
                "vs_baseline": xl["vs_baseline"],
                "details": {
                    "gpt2xl_1p6b_bf16": xl,
                    "gpt2small_124m_f32": small,
                    "resnet50_25m_f32": resnet,
                    "train_step_llama_350m_pallas": train,
                    "flash_attention_16k": flash16k,
                    "generate_llama_350m_decode": gen,
                    "serving_llama_350m_continuous": serving,
                    "fleet_failover": fleet,
                    "fleet_autoscale": autoscale,
                    "fleet_migration": migration,
                    "model_plane": model_plane,
                    "cold_uncached_s": cold,
                    "peak_rss_mb": round(_rss_mb(), 1),
                    "device": str(jax.devices()[0]),
                    # Whole-process counters/gauges from the telemetry
                    # registry — the numbers the system measured about
                    # itself (docs/observability.md has the catalog).
                    "telemetry": {
                        "counters": telemetry.counters(),
                        "gauges": telemetry.gauges(),
                    },
                },
            }
        )
    )
    failed = [
        name
        for name, row in (
            ("train", train), ("flash", flash16k), ("generate", gen),
            ("serving", serving), ("fleet", fleet),
            ("autoscale", autoscale), ("migration", migration),
            ("model_plane", model_plane), ("cold", cold),
        )
        if "error" in row
    ]
    if failed:
        print(f"bench.py: phases failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
