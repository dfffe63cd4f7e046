#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

    python chip_smoke.py          # from the checkout root, on a TPU machine

ONE process (a chip belongs to one process at a time; nothing started here
touches JAX) drives the main path once through the entry points a user
calls, at the published GPT-2-XL widths (48 layers, dim 1600, 25 heads of
64, ctx 1024, vocab 50257, bf16), weights random from a seed:

  gate        versions, platform, device kind/count, compile-cache
              directory, native core built from src/cc in this run
  paper_path  deferred_init(GPT2LMHeadModel) -> materialize_module_jax
  serve       convert -> serving.Engine answers overlapping requests;
              every greedy stream checked against a full reference
              forward, and counted against solo generate
  train       make_train_step(attn_impl="pallas") on a mesh of the
              devices present, S=1024, loss finite and falling
  kernels     the flash forward and the one-kernel backward, at one kv
              block and at several, compiled by Mosaic (not interpreted,
              not jnp) and agreeing with ops.attention.mha_reference
  four_chip   (>= 4 devices) shard-then-materialize over fsdp=4, shards
              on 4 distinct devices, per-device bytes near an even share

It fails rather than carry on on the CPU: when the platform is not ``tpu``
it exits 2 and prints no result.  A failed phase prints its traceback, the
remaining phases still run, and the exit code is 1.  The last line of
standard output is the result, one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
the device as JAX reports it.  The line before it, ``report: {...}``,
carries each phase's status and its wall time split into set-up (tracing,
compiling, loading) and run, plus the compile-cache counts.  No timing
printed here is a performance record — it says the path runs, not how fast.

``run(TINY, require_tpu=False)`` is the same code at a toy size for
debugging the control flow on the CPU (Pallas in interpret mode); it
never prints ``"ok": true``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import json
import os
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One model geometry plus how hard each phase drives it."""

    n_layer: int
    n_embd: int
    n_head: int
    n_positions: int
    vocab_size: int
    dtype: str  # materialize/serve/train dtype
    # serve: (prompt length, max_new_tokens) per request; the prompt
    # lengths span >= 2 prefill buckets and one exceeds prefill_chunk.
    requests: Tuple[Tuple[int, int], ...]
    num_slots: int
    max_model_len: int
    prefill_chunk: int
    # train: rows per device and steps after the compiling one.
    train_rows_per_device: int
    train_steps: int
    # AdamW on bf16 params with no warm-up: at 48 layers the loss on a
    # repeated batch bounces at 1e-4 and falls steadily at 1e-5 (measured
    # on the chip, PR 21); the toy takes a normal rate.
    train_lr: float
    # kernels: (seq, heads, head_dim, the backward these shapes build, as
    # ``attention.flash_bwd{kernel=...}`` counts it)
    kernel_cases: Tuple[Tuple[int, int, int, str], ...]


XL = Sizes(
    n_layer=48, n_embd=1600, n_head=25, n_positions=1024, vocab_size=50257,
    dtype="bfloat16",
    requests=((20, 24), (100, 32), (400, 40), (600, 16), (60, 48), (250, 8)),
    num_slots=8, max_model_len=1024, prefill_chunk=512,
    train_rows_per_device=2, train_steps=4, train_lr=1e-5,
    kernel_cases=(
        (1024, 25, 64, "fused_nk1"),
        (4096, 4, 64, "fused"),
        (4096, 4, 128, "fused"),
    ),
)

TINY = Sizes(
    n_layer=2, n_embd=64, n_head=4, n_positions=128, vocab_size=256,
    dtype="float32",
    requests=((5, 6), (20, 8), (40, 5), (70, 4)),
    num_slots=4, max_model_len=128, prefill_chunk=32,
    train_rows_per_device=2, train_steps=3, train_lr=3e-4,
    kernel_cases=((128, 4, 16, "fused_nk1"), (4096, 1, 16, "fused")),
)


def _say(msg: str = "") -> None:
    print(msg, flush=True)


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024
    return 0.0


def _gb(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


class _Clock:
    """Wall time of a phase, split into set-up and run."""

    def __init__(self):
        self.setup_s = self.run_s = 0.0

    def _timed(self, attr: str, fn: Callable[[], Any]) -> Any:
        t0 = time.perf_counter()
        out = fn()
        setattr(self, attr, getattr(self, attr) + time.perf_counter() - t0)
        return out

    def setup(self, fn: Callable[[], Any]) -> Any:
        """Tracing, compiling, loading, first calls."""
        return self._timed("setup_s", fn)

    def run(self, fn: Callable[[], Any]) -> Any:
        return self._timed("run_s", fn)


def _check(cond: bool, msg: str) -> None:
    # Not `assert`: the checks must hold under `python -O` too.
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def _delta(c1: Dict[str, int], c0: Dict[str, int], name: str) -> int:
    return c1.get(name, 0) - c0.get(name, 0)


# ---------------------------------------------------------------------------
# gate


def _gate(require_tpu: bool) -> dict:
    """Versions, device, native core, compile cache.  Returns the device
    record; exits (no result printed) when the run must not go on."""
    import jax
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — absent on a CPU-only install
        libtpu = "absent"
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    _say(
        f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  libtpu {libtpu}"
    )
    _say(
        f"platform={device['platform']}  device_kind={device['kind']!r}  "
        f"count={device['count']}"
    )
    if require_tpu and device["platform"] != "tpu":
        print(
            f"chip_smoke: platform is {device['platform']!r}, not 'tpu'; "
            "refusing to run any phase",
            file=sys.stderr,
        )
        raise SystemExit(2)

    # Build the native core from src/cc in THIS run: what git would commit
    # holds no .so, and a binary left on disk proves nothing about the
    # sources beside it.  (g++ is the only process started here, and it
    # needs no chip.)
    lib_dir = os.path.join(ROOT, "torchdistx_tpu", "lib")
    for path in glob.glob(os.path.join(lib_dir, "*.so*")):
        os.unlink(path)
    t0 = time.perf_counter()
    from torchdistx_tpu import _native  # builds _tdx_stack.so on import

    native_ok = _native.native_available() and _native.stack_ops() is not None
    _say(
        f"native core: available={native_ok} "
        f"(built from src/cc in {time.perf_counter() - t0:.1f}s)"
    )
    if not native_ok:
        print("chip_smoke: native core did not build", file=sys.stderr)
        raise SystemExit(3)

    import torchdistx_tpu.materialize as M
    from torchdistx_tpu import telemetry
    from torchdistx_tpu.utils import compilation_cache as cc

    cc.ensure_compilation_cache()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    errors = telemetry.counters().get("compile_cache.errors", 0)
    _say(
        f"compile cache: dir={jax.config.jax_compilation_cache_dir!r} "
        f"(JAX_COMPILATION_CACHE_DIR {'=' + env_dir if env_dir else 'unset'})"
        f"  executable tier: {M._exec_disk_dir() or 'off'}  "
        f"compile_cache.errors={errors}"
    )
    return device


def _watch_persistent_cache() -> Dict[str, int]:
    """Count JAX's own persistent-cache lookups and hits (its monitoring
    events) so a second process can show the cache hitting."""
    import jax

    seen = {"requests": 0, "hits": 0}

    def on_event(name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            seen["requests"] += 1
        elif name == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


# ---------------------------------------------------------------------------
# phases


def _hf_config(sz: Sizes):
    from transformers import GPT2Config

    return GPT2Config(
        n_layer=sz.n_layer, n_embd=sz.n_embd, n_head=sz.n_head,
        n_positions=sz.n_positions, vocab_size=sz.vocab_size,
    )


def _native_cfg(sz: Sizes):
    """The native config derived from the HF one — at XL it must BE the
    repo's published ``gpt2_xl()``."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import convert, gpt2

    cfg = convert.gpt2_config_from_hf(
        _hf_config(sz), dtype=getattr(jnp, sz.dtype),
    )
    if sz is XL:
        _check(cfg == gpt2.gpt2_xl(), f"{cfg} != gpt2_xl()")
    return cfg


def phase_paper_path(sz: Sizes, ctx: dict, clock: _Clock) -> dict:
    t0 = time.perf_counter()
    import jax
    import numpy as np
    import torch
    from transformers import GPT2LMHeadModel

    import torchdistx_tpu.deferred_init as di
    from torchdistx_tpu import telemetry
    from torchdistx_tpu.materialize import materialize_module_jax

    _say(f"imports (torch, transformers): {time.perf_counter() - t0:.1f}s")
    rss0 = _rss_mb()
    module = clock.run(
        lambda: di.deferred_init(GPT2LMHeadModel, _hf_config(sz))
    )
    n_params = sum(p.numel() for p in module.parameters())
    rss_growth = _rss_mb() - rss0
    _say(
        f"deferred_init: {n_params / 1e6:.1f}M params, host RSS growth "
        f"{rss_growth:.0f} MB"
    )
    c0 = telemetry.counters()

    def materialize():
        arrays = materialize_module_jax(
            module, dtype=getattr(torch, sz.dtype)
        )
        jax.block_until_ready(list(arrays.values()))
        return arrays

    arrays = clock.setup(materialize)
    c1 = telemetry.counters()

    platform = ctx["device"]["platform"]
    want = {
        n: tuple(p.shape)
        for n, p in list(module.named_parameters())
        + list(module.named_buffers())
    }
    for name, arr in arrays.items():
        _check(isinstance(arr, jax.Array), f"{name} is {type(arr)}")
        _check(
            all(d.platform == platform for d in arr.devices()),
            f"{name} on {arr.devices()}",
        )
        _check(str(arr.dtype) == sz.dtype, f"{name} dtype {arr.dtype}")
        _check(tuple(arr.shape) == want[name], f"{name} shape {arr.shape}")
    total = sum(a.nbytes for a in arrays.values())
    # The values are the init the module asked for: N(0, 0.02) embeddings,
    # unit LayerNorm scales.
    wte = np.asarray(arrays["transformer.wte.weight"], np.float32)
    _check(bool(np.isfinite(wte).all()), "wte not finite")
    _check(0.018 < float(wte.std()) < 0.022, f"wte std {wte.std():.4f}")
    ln = np.asarray(arrays["transformer.ln_f.weight"], np.float32)
    _check(bool((ln == 1.0).all()), "ln_f.weight is not all ones")

    fallback = _delta(c1, c0, "materialize.torch_fallback_params")
    compiles = _delta(c1, c0, "compile.count{program=materialize}")
    disk_hits = _delta(c1, c0, "materialize.exec_cache_disk_hits")
    _say(
        f"materialize: {len(arrays)} leaves, {_gb(total)} on "
        f"{platform}; torch_fallback_params={fallback} "
        f"compile.count{{program=materialize}}={compiles} "
        f"exec_cache_disk_hits={disk_hits}"
    )
    _check(fallback == 0, f"{fallback} params fell back to torch replay")
    _check(compiles >= 1, "no materialize program was compiled or loaded")
    ctx["arrays"] = arrays
    return {
        "params": n_params,
        "rss_growth_mb": round(rss_growth, 1),
        "leaves": len(arrays),
        "exec_cache_disk_hits": disk_hits,
    }


def phase_serve(sz: Sizes, ctx: dict, clock: _Clock) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.models import convert, gpt2
    from torchdistx_tpu.models.generate import generate
    from torchdistx_tpu.serving import Engine
    from torchdistx_tpu.telemetry import perf

    cfg = _native_cfg(sz)
    params = clock.setup(
        lambda: jax.block_until_ready(
            convert.gpt2_params_from_hf(ctx.pop("arrays"), cfg)
        )
    )
    gc.collect()

    c0 = telemetry.counters()
    eng = clock.setup(
        lambda: Engine(
            params, model=gpt2, cfg=cfg, num_slots=sz.num_slots,
            max_model_len=sz.max_model_len, prefill_chunk=sz.prefill_chunk,
        )
    )
    try:
        ledger = perf.ledger.components()
        stats = jax.devices()[0].memory_stats() or {}
        in_use = stats.get("bytes_in_use")
        _say(
            f"engine: {sz.num_slots} slots, {eng.allocator.capacity} pages "
            f"of {eng.block_size}; HBM ledger {ledger} = "
            f"{_gb(sum(ledger.values()))}; memory_stats bytes_in_use="
            f"{_gb(in_use) if in_use else 'n/a'} of "
            f"{_gb(stats.get('bytes_limit', 0))}"
            + (
                f" (in_use / ledger = {in_use / sum(ledger.values()):.2f}x"
                " — the ledger counts logical shapes, the chip tiles them)"
                if in_use else ""
            )
        )
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
            for plen, _ in sz.requests
        ]

        def submit(i):
            return eng.submit(
                prompts[i], max_new_tokens=sz.requests[i][1], key=i
            )

        def drive():
            # Half the requests, a few ticks, then the rest: the late
            # ones prefill while the early ones decode.
            half = len(prompts) // 2
            handles = [submit(i) for i in range(half)]
            for _ in range(3):
                eng.step()
            handles += [submit(i) for i in range(half, len(prompts))]
            return [h.result() for h in handles]

        # Every program here compiles on first use, so the first drive is
        # set-up.  The second finds every prompt in the prefix cache (other
        # prefill shapes); the third repeats the second's shapes exactly.
        outs = clock.setup(drive)
        for (plen, n), toks in zip(sz.requests, outs):
            _check(len(toks) == n, f"prompt {plen}: {len(toks)} of {n} tokens")
        hits0 = eng.stats()["prefix_hits"]
        outs_hit = clock.run(drive)
        _check(eng.stats()["prefix_hits"] > hits0, "no prefix-cache hit")
        _check(
            clock.run(drive) == outs_hit,
            "same requests, same keys, same compiled shapes, other tokens",
        )
        c1 = telemetry.counters()
        decode_compiles = _delta(c1, c0, "compile.count{program=decode_chunk}")
        buckets = sorted(
            k for k in c1
            if k.startswith("compile.count{program=prefill_chunk")
            and _delta(c1, c0, k)
        )
        st = eng.stats()
        _say(
            f"served {3 * len(outs)} requests, {st['decode_tokens']} decode "
            f"tokens in {st['ticks']} ticks, {st['prefix_hits']} prefix "
            f"hits; decode_chunk compiles={decode_compiles}; prefill "
            "programs: "
            + ", ".join(b[len("compile.count{program="):-1] for b in buckets)
        )
        _check(
            decode_compiles == 1, f"decode_chunk compiled {decode_compiles}x"
        )
        n_buckets = len({b.split(":b")[1] for b in buckets})
        _check(n_buckets >= 2, f"{n_buckets} prefill bucket(s)")

        # Solo generate, same key, same model, for every request.
        solos = clock.setup(
            lambda: [
                np.asarray(
                    generate(
                        params, jnp.asarray(prompts[i])[None],
                        jax.random.PRNGKey(i), model=gpt2, cfg=cfg,
                        max_new_tokens=n,
                    )
                )[0].tolist()
                for i, (_, n) in enumerate(sz.requests)
            ]
        )

        # The reference: ONE full forward (no KV cache, no pages, its own
        # attention path) over prompt + generated tokens for every stream
        # seen above.  Greedy means each generated token is the argmax at
        # its position — up to rounding: in bf16 two programs of different
        # shapes (another prefill bucket, batch 1 vs 8 slots) may round a
        # logit differently, so between near-tied candidates either may
        # win and the streams part there.  Every token must be within
        # ``tol`` (4 bf16 ulps of the largest logit; 1e-4 in f32) of the
        # reference's best, which is what "right" means for a greedy
        # stream; token identity is then counted, not assumed.
        streams = [
            (f"{kind}[{i}]", prompts[i], toks)
            for kind, group in (
                ("engine", outs), ("prefix-hit", outs_hit), ("solo", solos)
            )
            for i, toks in enumerate(group)
            if kind == "engine" or toks != outs[i]
        ]
        width = -(-max(len(p) + len(t) for _, p, t in streams) // 128) * 128
        batch = np.zeros((len(streams), width), np.int32)
        for row, (_, p, t) in zip(batch, streams):
            row[: len(p)] = p
            row[len(p): len(p) + len(t)] = t
        logits = clock.setup(
            lambda: np.asarray(
                jax.jit(functools.partial(gpt2.forward, cfg=cfg))(
                    params, jnp.asarray(batch)
                )
            )
        )
        _check(
            logits.shape == (len(streams), width, cfg.vocab_size),
            f"logits shape {logits.shape}",
        )
        _check(bool(np.isfinite(logits).all()), "logits not finite")
        top = float(np.abs(logits).max())
        tol = (
            4 * 2.0 ** (np.floor(np.log2(top)) - 7)
            if sz.dtype == "bfloat16" else 1e-4
        )
        worst = 0.0
        for lg, (name, p, t) in zip(logits, streams):
            pos = np.arange(len(p) - 1, len(p) - 1 + len(t))
            gap = lg[pos].max(axis=-1) - lg[pos, np.asarray(t)]
            worst = max(worst, float(gap.max()))
            _check(
                float(gap.max()) <= tol,
                f"{name}: token {int(gap.argmax())} is {gap.max():.4f} below "
                f"the reference's best logit (tol {tol:.4f})",
            )
        same_solo = sum(a == b for a, b in zip(outs, solos))
        same_hit = sum(a == b for a, b in zip(outs, outs_hit))
        _say(
            f"reference forward {logits.shape}: finite, max |logit| "
            f"{top:.2f}; all {len(streams)} streams within {worst:.4f} of "
            f"the reference argmax (tol {tol:.4f})"
        )
        _say(
            f"token-identical streams: engine vs solo generate {same_solo}/"
            f"{len(outs)}, engine cold vs prefix-hit {same_hit}/{len(outs)}"
            " (streams that part do so between candidates within tol)"
        )
        _check(same_solo >= 1, "no stream token-identical to solo generate")
    finally:
        eng.close()
    return {
        "requests": 3 * len(outs),
        "decode_chunk_compiles": decode_compiles,
        "prefill_buckets": n_buckets,
        "identical_to_solo": f"{same_solo}/{len(outs)}",
        "identical_cold_vs_prefix_hit": f"{same_hit}/{len(outs)}",
        "worst_gap_to_reference_argmax": round(worst, 5),
        "tolerance": float(tol),
        "ledger_bytes": sum(ledger.values()),
        "bytes_in_use": in_use,
    }


def _train_bytes(cfg, rows: int, seq: int, n_dev: int) -> int:
    """Bytes per device one train step needs, from shapes: this device's
    share of params, grads and the two AdamW moments, and for its ``rows``
    of the batch the per-layer residuals remat keeps (the block's input,
    the flash kernel's output and its f32 log-sum-exp) plus the logits
    (stored + f32 softmax + its gradient)."""
    from torchdistx_tpu.models import gpt2

    itemsize = 2 if "bfloat16" in str(cfg.dtype) else 4
    state = 4 * gpt2.num_params(cfg) * itemsize // n_dev
    resid = cfg.n_layers * rows * seq * (
        2 * cfg.dim * itemsize + 4 * cfg.n_heads
    )
    logits = rows * seq * cfg.vocab_size * (itemsize + 8)
    return state + resid + logits


def phase_train(sz: Sizes, ctx: dict, clock: _Clock) -> dict:
    import jax
    import optax

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.models import gpt2
    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    # The earlier phases' weights must be gone before the optimizer state
    # arrives (the serve phase pops them unless it failed early).
    ctx.pop("arrays", None)
    gc.collect()

    n_dev = ctx["device"]["count"]
    on_tpu = ctx["device"]["platform"] == "tpu"
    mesh = make_mesh(MeshSpec(fsdp=n_dev))
    rows, seq = sz.train_rows_per_device * n_dev, sz.n_positions
    cfg = _native_cfg(sz)
    # Full depth: by shapes it fits one 16 GB chip (and XLA:TPU's own
    # memory analysis agrees, PERF.md section 5), so depth is not cut.
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    _say(
        f"train: depth {cfg.n_layers}, {rows}x{seq} tokens over mesh "
        f"{dict(mesh.shape)}: ~"
        f"{_gb(_train_bytes(cfg, sz.train_rows_per_device, seq, n_dev))} "
        "per device by shapes" + (f" of {_gb(limit)}" if limit else "")
    )
    c0 = telemetry.counters()
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(sz.train_lr), model=gpt2, attn_impl="pallas"
    )
    state = clock.setup(
        lambda: jax.block_until_ready(init_fn(jax.random.PRNGKey(0)))
    )
    tokens = jax.device_put(
        jax.random.randint(
            jax.random.PRNGKey(1), (rows, seq), 0, cfg.vocab_size
        ),
        ts.batch_sharding(mesh),
    )
    batch = {"tokens": tokens, "targets": tokens}
    hlo = clock.setup(lambda: step_fn.lower(state, batch).as_text())
    losses: List[float] = []

    def one_step():
        nonlocal state
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        _check(not bool(metrics["nonfinite"]), "non-finite step")

    clock.setup(one_step)  # compiles
    for _ in range(sz.train_steps):
        clock.run(one_step)
    c1 = telemetry.counters()
    _say("train losses: " + ", ".join(f"{x:.4f}" for x in losses))
    _check(all(x == x and abs(x) < 1e9 for x in losses), "loss not finite")
    _check(losses[-1] < losses[0], "loss did not fall on a repeated batch")

    # Which attention ran — resolved at trace time and counted.
    picked = {
        k for k in c1
        if k.startswith(("attention.dispatch{", "attention.flash{"))
        and _delta(c1, c0, k)
    }
    n_custom = hlo.count("tpu_custom_call")
    _say(
        f"train step attention: {sorted(picked)}; tpu_custom_call "
        f"x{n_custom} in HLO"
    )
    want = {
        "attention.dispatch{impl=pallas}",
        "attention.flash{interpret=%s}" % ("false" if on_tpu else "true"),
    }
    _check(picked == want, f"attention resolved to {picked}, wanted {want}")
    if on_tpu:
        # forward + fused backward, each a Mosaic custom call.
        _check(n_custom >= 2, f"{n_custom} TPU custom calls in the step")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    if peak:
        _say(
            f"memory_stats peak_bytes_in_use (device 0, whole process) "
            f"{_gb(peak)}"
        )
    ctx["train_devices"] = n_dev
    return {
        "depth": cfg.n_layers,
        "mesh": dict(mesh.shape),
        "tokens_per_step": rows * seq,
        "losses": [round(x, 4) for x in losses],
        "tpu_custom_calls": n_custom,
    }


def phase_kernels(sz: Sizes, ctx: dict, clock: _Clock) -> dict:
    """The kernels, called directly, against ``mha_reference``.

    One kv block (S <= ``_FUSED_BWD_MAX_KV``) and several run forward + a
    one-kernel backward (2 Mosaic calls); the streamed dq and dk/dv pair
    (3) takes only shapes whose dq outgrows ``_FUSED_BWD_DQ_VMEM``, too
    long for a dense reference: the CPU tests and the compile for a
    described v5e cover it.
    Tolerance: the kernel computes in bf16 with f32 accumulation and the
    reference in f32 at ``highest`` precision, so the output and each
    gradient must agree to 2% of the reference's largest magnitude (about
    5 bf16 ulps); 1e-4 when the inputs are f32.
    """
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.ops.attention import mha_reference
    from torchdistx_tpu.ops.pallas import flash_attention as fa

    on_tpu = ctx["device"]["platform"] == "tpu"
    dtype = getattr(jnp, sz.dtype)
    tol = 2e-2 if sz.dtype == "bfloat16" else 1e-4
    report = {}
    for s, h, d, bwd in sz.kernel_cases:
        keys = jax.random.split(jax.random.PRNGKey(s + d), 4)
        q, k, v = (
            jax.random.normal(kk, (1, s, h, d), dtype=dtype)
            for kk in keys[:3]
        )
        w = jax.random.normal(keys[3], (1, s, h, d), dtype=jnp.float32)

        def loss(fn, q, k, v):
            out = fn(q, k, v, causal=True).astype(jnp.float32)
            return (out * w).sum(), out

        def vg(fn):
            return jax.jit(
                jax.value_and_grad(
                    functools.partial(loss, fn), argnums=(0, 1, 2),
                    has_aux=True,
                )
            )

        kernel = vg(fa.flash_attention)
        built = f"attention.flash_bwd{{kernel={bwd}}}"
        n_built = telemetry.counters().get(built, 0)
        hlo = clock.setup(lambda: kernel.lower(q, k, v).as_text())
        _check(
            telemetry.counters().get(built, 0) == n_built + 1,
            f"S={s} did not build the {bwd} backward",
        )
        n_custom = hlo.count("tpu_custom_call")
        got = clock.setup(lambda: jax.block_until_ready(kernel(q, k, v)))

        def reference():
            with jax.default_matmul_precision("highest"):
                return jax.block_until_ready(
                    vg(mha_reference)(
                        *(x.astype(jnp.float32) for x in (q, k, v))
                    )
                )

        ref = clock.setup(reference)
        errs = {}
        for name, a, b in zip(
            ("out", "dq", "dk", "dv"),
            (got[0][1], *got[1]),
            (ref[0][1], *ref[1]),
        ):
            _check(bool(jnp.isfinite(a).all()), f"{name} not finite")
            errs[name] = float(
                jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max()
            )
        label = f"S={s} H={h} d={d} {bwd}"
        _say(
            f"flash {label}: tpu_custom_call x{n_custom}; rel err vs "
            "mha_reference "
            + " ".join(f"{k}={e:.2e}" for k, e in errs.items())
            + f" (tol {tol:g})"
        )
        if on_tpu:
            want = 3 if bwd == "pair" else 2
            _check(
                n_custom == want,
                f"{label}: {n_custom} Mosaic calls, expected {want}",
            )
        bad = {k: e for k, e in errs.items() if not e <= tol}
        _check(not bad, f"{label}: beyond tolerance {tol}: {bad}")
        report[label] = {k: float(f"{e:.3g}") for k, e in errs.items()}
    return {"cases": report, "tolerance": tol}


def phase_four_chip(sz: Sizes, ctx: dict, clock: _Clock) -> dict:
    import jax
    import torch
    from transformers import GPT2LMHeadModel

    import torchdistx_tpu.deferred_init as di
    from torchdistx_tpu.materialize import materialize_module_jax
    from torchdistx_tpu.parallel import fsdp_plan
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    n_dev = ctx["device"]["count"]
    if n_dev < 4:
        _say(f"skipped: {n_dev} device(s)")
        return {"status": f"skipped: {n_dev} device(s)"}

    devices = jax.devices()[:4]
    mesh = make_mesh(MeshSpec(fsdp=4), devices=devices)
    min_size = 1024
    plan = fsdp_plan(min_size=min_size)
    module = di.deferred_init(GPT2LMHeadModel, _hf_config(sz))
    gc.collect()

    def in_use():
        return [
            (d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices
        ]

    before = in_use()

    def materialize():
        arrays = materialize_module_jax(
            module, mesh=mesh, plan=plan, dtype=getattr(torch, sz.dtype)
        )
        jax.block_until_ready(list(arrays.values()))
        return arrays

    arrays = clock.setup(materialize)
    after = in_use()
    total = sum(a.nbytes for a in arrays.values())
    n_sharded = 0
    for name, arr in arrays.items():
        if arr.size < min_size:
            continue
        shards = arr.addressable_shards
        _check(
            len({s.device for s in shards}) == 4,
            f"{name}: shards on {[s.device for s in shards]}",
        )
        if any(ax is not None for ax in arr.sharding.spec):
            n_sharded += 1
            _check(
                all(s.data.size * 4 == arr.size for s in shards),
                f"{name}: uneven shards {[s.data.shape for s in shards]}",
            )
    held = [a - b for a, b in zip(after, before)]
    share = total / 4
    _say(
        f"sharded materialize: {len(arrays)} leaves, {_gb(total)} total, "
        f"{n_sharded} leaves split four ways; per-device bytes "
        + ", ".join(_gb(x) for x in held)
        + f" (even share {_gb(share)})"
    )
    _check(n_sharded > 0, "the plan sharded nothing")
    if any(after):  # memory_stats is None on the CPU backend
        _check(
            max(held) <= 1.5 * share,
            f"one device holds {max(held) / share:.2f}x its share",
        )
    # The train phase ran full depth over the mesh of all devices: on this
    # host that is the four-chip training run.
    _check(
        ctx.get("train_devices", 0) >= 4,
        "the train phase did not complete on four devices",
    )
    return {
        "per_device_bytes": held,
        "even_share_bytes": int(share),
        "leaves_sharded": n_sharded,
    }


PHASES = (
    ("paper_path", phase_paper_path),
    ("serve", phase_serve),
    ("train", phase_train),
    ("kernels", phase_kernels),
    ("four_chip", phase_four_chip),
)


def run(sz: Sizes = XL, require_tpu: bool = True) -> int:
    t_start = time.perf_counter()
    device = _gate(require_tpu)
    cache = _watch_persistent_cache()

    from torchdistx_tpu import telemetry

    ctx: Dict[str, Any] = {"device": device}
    phases: Dict[str, dict] = {}
    for name, fn in PHASES:
        _say(f"\n== {name}")
        clock = _Clock()
        t0 = time.perf_counter()
        try:
            info = fn(sz, ctx, clock)
            status = info.pop("status", "ok")
        except Exception as e:  # noqa: BLE001 — later phases still run
            traceback.print_exc(file=sys.stdout)
            status = "failed"
            info = {"error": f"{type(e).__name__}: {e}"[:300]}
        phases[name] = {
            "status": status,
            "wall_s": round(time.perf_counter() - t0, 2),
            "setup_s": round(clock.setup_s, 2),
            "run_s": round(clock.run_s, 2),
            **info,
        }
        _say(f"-- {name}: {status} ({phases[name]['wall_s']}s)")
        gc.collect()

    counters = telemetry.counters()
    cache_report = {
        "persistent_requests": cache["requests"],
        "persistent_hits": cache["hits"],
        "exec_cache_disk_hits": counters.get(
            "materialize.exec_cache_disk_hits", 0
        ),
        "compile_cache_errors": counters.get("compile_cache.errors", 0),
    }
    _say(f"\ncompile cache this process: {cache_report}")
    failed = [n for n, p in phases.items() if p["status"] == "failed"]
    # "ok" only for the real thing: every phase at the real size on a TPU.
    ok = not failed and require_tpu and sz is XL
    report = {
        "failed": failed,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "cache": cache_report,
        "phases": phases,
    }
    _say("report: " + json.dumps(report))
    # The last line is the result, and nothing but: exactly these keys.
    _say(json.dumps({"ok": ok, "device": device}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(run())
