#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found BY NAME: ``--workload X`` opens
``workloads/X.json``, which names its configuration (``configs/``), its
driver (``drivers/``) and the metrics it reports (``metrics/``, each naming
its reader in ``readers/``); the configuration names its family
(``families/``), which names its plain reference (``reference/``).  A later
PR adds any of these as new files and edits none that is here.

The run: set-up (imports, native core, compile cache, the paper's path
``deferred_init`` -> ``materialize_module_jax`` from ``--seed``, convert,
build, warm-up, pre-roll) -> the measured window of ``--seconds`` -> the
reference check -> the result, ONE JSON object on the last line of standard
output with exactly ``correct, attempted, failed, metrics, device`` (and
``breakdown`` when traced).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics with a profiler trace over a
few seconds of the window.  It fails (exit 2, no result) when JAX finds no
TPU or fewer chips than the cell asks for; ``--rehearse`` is the harness's
own CPU path: each file's ``tiny`` block, ``platform: cpu``, and no value
under any metric's name.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p in sys.path:
        sys.path.remove(_p)
    sys.path.insert(0, _p)

REHEARSE_AS = "TPU v5 lite"


def say(msg: str = "") -> None:
    print(msg, flush=True)


def read_json(*parts: str):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (
            merge(out[k], v)
            if isinstance(v, dict) and isinstance(out.get(k), dict) else v
        )
    return out


class Setup:
    """Where every second of set-up goes, from process start (``T0``)."""

    def __init__(self):
        self.parts = []

    @contextlib.contextmanager
    def part(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.parts.append((name, time.perf_counter() - t))

    def seconds(self, *names: str) -> float:
        return sum(s for n, s in self.parts if n in names)

    def report(self, total: float) -> str:
        rows = self.parts + [
            ("(not inside any part)", total - sum(s for _, s in self.parts))
        ]
        return "\n".join(f"  {s:8.2f} s  {n}" for n, s in rows)


class Tracer:
    """The profiler over a few seconds of the window (``--trace 1`` only),
    and the benchmark's own host spans around its calls into the program."""

    def __init__(self, on: bool, start_s: float, length_s: float):
        self.on, self.start_s, self.length_s = on, start_s, length_s
        self.dir = self.t0 = self.t1 = self.wall0 = self.wall1 = None
        self._window = None
        self.overhead_s = 0.0

    @property
    def active(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def tick(self, elapsed: float) -> None:
        """Called by the driver once per loop turn with the seconds since
        the window began."""
        if not self.on:
            return
        if self.t0 is None and elapsed >= self.start_s:
            import jax

            from benchlib import trace

            t = time.perf_counter()
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self._window = jax.profiler.TraceAnnotation(trace.WINDOW)
            self._window.__enter__()
            self.t0, self.wall0 = time.perf_counter(), time.time()
            self.overhead_s += self.t0 - t
        elif self.active and elapsed >= self.start_s + self.length_s:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self.t1, self.wall1 = time.perf_counter(), time.time()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - self.t1

    def reduce(self):
        """After the window: the trace's reduction, or None."""
        if self.dir is None:
            return None
        from benchlib import trace

        self.stop()
        try:
            t = time.perf_counter()
            path = trace.find_xplane(self.dir)
            size = os.path.getsize(path)
            out = trace.reduce(path)
            say(
                f"trace: {size / 1e6:.1f} MB reduced in "
                f"{time.perf_counter() - t:.1f}s; starting and stopping the "
                f"profiler held the loop for {self.overhead_s:.1f}s"
            )
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def gate(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(f"jax {jax.__version__}  device {device}")
    if rehearse:
        say("platform: cpu (rehearsal: no value is reported under any metric)")
    elif device["platform"] != "tpu" or device["count"] < chips:
        print(
            f"benchmarks/run.py: the cell needs {chips} TPU chip(s), JAX "
            f"found {device}; refusing to run", file=sys.stderr,
        )
        raise SystemExit(2)
    return device


def open_compile_cache() -> dict:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), admitting every program however quick to
    compile: a run makes dozens of sub-second ones, and each miss is paid in
    every run.  Returns the live count of lookups and hits."""
    import jax

    from torchdistx_tpu.utils import compilation_cache as cc

    seen = {"requests": 0, "hits": 0}

    def on_event(name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            seen["requests"] += 1
        elif name == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    cc.ensure_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(f"compile cache: {jax.config.jax_compilation_cache_dir!r}")
    return seen


def make_params(cell, setup=None):
    """The paper's path, in every cell: ``deferred_init`` of the Hugging
    Face module, ``materialize_module_jax`` onto the device from the seed in
    the served dtype (one compiled program, never leaf by leaf on the host),
    then the family's conversion to the native stacked layout."""
    import jax
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    part = setup.part if setup is not None else (
        lambda name: contextlib.nullcontext()
    )
    with part("deferred_init"):
        cls, hf_config = cell.family.hf(cell.config)
        module = di.deferred_init(cls, hf_config)
        n_params = sum(p.numel() for p in module.parameters())
    with part("materialize"):
        arrays = M.materialize_module_jax(
            module, seed=cell.seed % (2**31 - 1),
            dtype=getattr(torch, cell.config["dtype"]),
        )
        jax.block_until_ready(list(arrays.values()))
    profile = {
        k: round(v, 3) for k, v in M.last_profile.items()
        if isinstance(v, float)
    }
    with part("convert"):
        params = cell.family.to_params(arrays, cell.cfg)
        del arrays, module
        jax.block_until_ready(params)
    if setup is not None:
        say(f"weights: {n_params / 1e6:.1f}M parameters; materialize {profile}")
    return params


def load_cell(name: str, seed: int, rehearse: bool):
    import jax.numpy as jnp

    workload = read_json("workloads", f"{name}.json")
    config = read_json("configs", f"{workload['config']}.json")
    if rehearse:
        workload = merge(workload, workload.get("tiny", {}))
        config = merge(config, config.get("tiny", {}))
    family = importlib.import_module(f"families.{config['family']}")
    model, cfg = family.native(config, getattr(jnp, config["dtype"]))
    check = importlib.import_module("reference.check")
    return types.SimpleNamespace(
        name=name, seed=seed, rehearse=rehearse, workload=workload,
        config=config, chips=workload["chips"], family=family, model=model,
        cfg=cfg, check=check, ref=check.load(family.REFERENCE),
        counts=family.counts(config),
        itemsize=jnp.dtype(config["dtype"]).itemsize,
    )


def read_metrics(names, run) -> dict:
    out = {}
    for name in names:
        spec = read_json("metrics", f"{name}.json")
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def program_faults(c0: dict, c1: dict, platform: str) -> list:
    """What makes a run incorrect whatever its tokens say: a program
    compiled (or loaded) inside pre-roll + window, a flash kernel that ran
    interpreted on a TPU, a compile-cache failure."""
    grew = {
        k: c1[k] - c0.get(k, 0) for k in c1
        if k.startswith("compile.count{") and c1[k] > c0.get(k, 0)
    }
    faults = [f"compiled inside pre-roll + window: {grew}"] if grew else []
    if platform == "tpu" and c1.get("attention.flash{interpret=true}", 0):
        faults.append("a flash kernel ran in interpret mode on a TPU")
    return faults


def run_cell(args) -> dict:
    setup = Setup()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    with setup.part("import jax"):
        import jax
    cell = load_cell(args.workload, args.seed, args.rehearse)
    device = gate(cell.chips, args.rehearse)
    with setup.part("import torch, transformers"):
        import torch  # noqa: F401
        import transformers  # noqa: F401
    with setup.part("import torchdistx_tpu, native core"):
        from torchdistx_tpu import _native, telemetry

        if not (_native.native_available() and _native.stack_ops()):
            raise RuntimeError("the native core did not build")
    with setup.part("compile cache"):
        cache = open_compile_cache()
        errors0 = telemetry.counters().get("compile_cache.errors", 0)
    tr = cell.workload.get("trace", {})
    tracer = Tracer(
        bool(args.trace), tr.get("start_s", 2.0), tr.get("length_s", 4.0)
    )
    if tracer.on:
        # The program's own spans into the profiler's trace and into memory.
        telemetry.configure(
            collect=True, jax_annotations=True, max_spans=500_000
        )

    cell.make_params = lambda: make_params(cell)
    params = make_params(cell, setup)
    driver = importlib.import_module(f"drivers.{cell.workload['driver']}")
    with setup.part("build + warm-up"):
        state = driver.build(cell, params)
    del params
    gc.collect()

    result = driver.run(cell, state, float(args.seconds), tracer)
    c1 = telemetry.counters()
    tracer.stop()
    setup.parts.append(
        (result["lead_in"], result["window_start"] - result["run_start"])
    )
    setup_s = result["window_start"] - T0
    peak = max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
         for d in jax.devices()), default=0,
    )
    say(f"set-up {setup_s:.2f} s, of which:\n{setup.report(setup_s)}")
    say(
        f"persistent compile cache: {cache['hits']} hits of "
        f"{cache['requests']} lookups"
    )
    for line in result.get("log", ()):
        say(line)

    trace = tracer.reduce()
    ok, detail = driver.check(cell, state, result)
    say(f"reference check: {detail}")
    faults = program_faults(result["counters0"], c1, device["platform"])
    if telemetry.counters().get("compile_cache.errors", 0) > errors0:
        faults.append("compile_cache.errors rose")
    for f in faults:
        say(f"FAULT: {f}")

    counts = dict(result["counts"])
    counts.update(
        setup_s=setup_s,
        materialize_s=setup.seconds("deferred_init", "materialize"),
        warmup_s=setup.seconds("build + warm-up"),
    )
    if tracer.t0 is not None:
        counts.update(driver.traced_counts(cell, result, tracer, telemetry))
    run = {
        "cell": cell, "counts": counts, "records": result.get("records", []),
        "trace": trace, "device": device,
        # A rehearsal exercises the readers against the peaks of the chip
        # the cells are written for; its values are never printed.
        "peaks_kind": REHEARSE_AS if args.rehearse else device["kind"],
    }
    kind = "per_layer" if args.trace else "end_to_end"
    other = "end_to_end" if args.trace else "per_layer"
    metrics = read_metrics(cell.workload[kind], run)
    also = read_metrics(cell.workload[other], run)
    if args.rehearse:
        # A CPU run proves the control flow; it gives no device number.
        metrics, also = (
            {k: {"value": None, "unit": v["unit"]} for k, v in m.items()}
            for m in (metrics, also)
        )
    say(f"also measured in this run ({other}): " + json.dumps(also))
    device["memory_peak_bytes"] = int(peak)
    line = {
        "correct": bool(ok and not faults),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
        say("programs in the traced window: " + json.dumps(trace["programs"]))
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    line = run_cell(args)
    say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
