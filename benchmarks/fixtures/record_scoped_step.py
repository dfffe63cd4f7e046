#!/usr/bin/env python3
"""Record ``scoped_step.xplane.pb`` and ``scoped_step.scopes.json``: a few
train steps of a small scanned GPT-2 (two layers under ``lax.scan``, remat,
Pallas flash attention, AdamW, the non-finite guard) on one TPU chip, with
the program's scope map for the operations the trace holds.

    chiprun -- python3 benchmarks/fixtures/record_scoped_step.py

writes both files under ``chiprun_out/``.  Then, in the sandbox,

    python3 benchmarks/fixtures/record_scoped_step.py --strip \\
        chiprun_out/scoped_step.xplane.pb benchmarks/fixtures/scoped_step.xplane.pb

keeps the device's and the host's planes without the copy of the whole HLO
module and the source stacks (1.3 MB -> 0.3 MB; it reads the file with
tensorflow's ``xplane_pb2``, which is why it is a step of its own: the
process that holds the chip does not import tensorflow).  Move the
``.scopes.json`` beside it as it is.  The trace is what
``benchlib/scopes.py`` is checked on (``tests/test_scopes.py``), so record
it again when the scopes of the train step change.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

STEPS = 3


def strip(src: str, dst: str) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = [
        p for p in space.planes
        if p.name.startswith("/device:TPU:") or p.name == "/host:CPU"
    ]
    del space.planes[:]
    for plane in keep:
        drop = {
            k for k, v in plane.stat_metadata.items() if v.name == "source_stack"
        }
        for meta in plane.event_metadata.values():
            stats = [s for s in meta.stats if s.metadata_id not in drop]
            del meta.stats[:]
            meta.stats.extend(stats)
        space.planes.append(plane)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())
    print(f"{os.path.getsize(src)} -> {os.path.getsize(dst)} bytes")
    return 0


def main() -> int:
    if sys.argv[1:2] == ["--strip"]:
        return strip(*sys.argv[2:4])
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchlib import scopes, trace
    from torchdistx_tpu import telemetry
    from torchdistx_tpu.models import gpt2
    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh
    from torchdistx_tpu.telemetry import perf

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_step.py: needs a TPU", file=sys.stderr)
        return 2
    telemetry.configure(collect=True)  # a sink: the step records its map
    cfg = gpt2.GPT2Config(
        vocab_size=512, dim=256, n_layers=2, n_heads=4, max_seq_len=256,
        dtype=jnp.bfloat16, layer_unroll=1,
    )
    mesh = make_mesh(MeshSpec(fsdp=1), devices=jax.devices()[:1])
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-4), model=gpt2, attn_impl="pallas"
    )
    state = init_fn(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 512, (2, 257)).astype(np.int32)
    batch = jax.device_put(
        {"tokens": ids[:, :-1], "targets": ids[:, 1:]}, ts.batch_sharding(mesh)
    )
    for _ in range(2):
        state, metrics = step_fn(state, batch)
    jax.block_until_ready(metrics)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for _ in range(STEPS):
                state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics)
        jax.profiler.stop_trace()
        pb = os.path.join(out, "scoped_step.xplane.pb")
        shutil.copy(trace.find_xplane(d), pb)

    from jax.profiler import ProfileData

    seen = {
        scopes.instruction(e.name)
        for plane in ProfileData.from_file(pb).planes
        if plane.name.startswith("/device:TPU:")
        for line in plane.lines if line.name == "XLA Ops"
        for e in line.events
    }
    full = perf.program_scopes()["train_step"]
    maps = {"step_fn": {k: list(v) for k, v in full.items() if k in seen}}
    with open(os.path.join(out, "scoped_step.scopes.json"), "w") as f:
        json.dump(maps, f, indent=0, sort_keys=True)
    print(
        f"{os.path.getsize(pb)} bytes of trace; {len(seen)} distinct "
        f"operations, {len(maps['step_fn'])} of them in the scope map of "
        f"{len(full)} instructions; loss {float(metrics['loss']):.4f}"
    )
    print(scopes.table("step_fn", scopes.reduce(pb, maps)["step_fn"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
