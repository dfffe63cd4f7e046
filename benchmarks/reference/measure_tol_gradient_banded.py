#!/usr/bin/env python3
"""The readings on either side of ``tol.gradient`` and
``tol.gradient_rows`` of a window/full-attention routed-expert training
cell (``drivers/train_steps_routed_queued``, family ``afmoe``); not run by
the driver.

    python3 benchmarks/reference/measure_tol_gradient_banded.py --workload <cell> [--seeds 1]
    python3 benchmarks/reference/measure_tol_gradient_banded.py --workload <cell> --fault <name> [--seed n]

Without ``--fault``: the plain reference's gradient on the cell's shapes,
float32 ``highest``, against the SAME reference computed worse, each
through the driver's own comparison (``compare``, ``gradient_ok``):

* ``bf16``: bfloat16 at default precision, what rounding alone does (the
  program is expected to read about this);
* ``window_causal``: float32, the window layers attend the whole triangle;
* ``edge_block``: float32, the band's lower edge unmasked: every key of the
  kernels' edge block is visible (blocks of ``BLOCK`` positions);
* ``rope_full``: float32, rope on the full layer too;
* ``no_gate``: float32, the sigmoid output gate left out;
* ``no_q_norm``: float32, the q heads' RMS norm left out;
* ``fp8_experts``: bfloat16, the operands of the expert products rounded to
  float8 e4m3's three mantissa bits (``measure_tol_gradient.fp8``);
* ``skip_expert``: float32, the routed sum leaves out one held expert.

Every control has to come out NOT correct.  Parameters come from the
family's ``init_params``, not the paper's path: 1 s against 25.

With ``--fault``: one run of the whole harness (``run.py``'s ``main``,
``--seconds 5``) with that fault put into the PROGRAM, which has to print
``"correct": false``.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402
from reference import measure_tol_gradient as shared  # noqa: E402

FAULTS = (
    "window_causal", "edge_block", "rope_full", "no_gate", "no_q_norm",
    "fp8_experts", "skip_expert",
)
# The kernels' block: the edge block of a q block is the lowest of its band.
BLOCK = 1024
WINDOW = "sliding_attention"


def fault_the_reference(ref, name: str, sizes):
    """Put ``name`` into the reference module; returns the undo."""
    saved = {
        k: getattr(ref, k)
        for k in ("_visible", "_attn", "_gated", "_rms", "_swiglu", "routed")
    }

    def undo():
        for k, v in saved.items():
            setattr(ref, k, v)

    visible, attn, rms = ref._visible, ref._attn, ref._rms
    if name == "window_causal":
        ref._visible = lambda rows, cols, window: visible(rows, cols, None)
    elif name == "edge_block":
        def edge_visible(rows, cols, window):
            plain = visible(rows, cols, None)
            if window is None:
                return plain
            first = (rows // BLOCK * BLOCK - window + 1) // BLOCK * BLOCK
            return plain & (cols[None, :] >= first[:, None])

        ref._visible = edge_visible
    elif name == "rope_full":
        ref._attn = lambda x, lp, sizes, kind: attn(
            x, lp,
            sizes if kind == WINDOW else dict(sizes, sliding_window=1 << 30),
            WINDOW,
        )
    elif name == "no_gate":
        ref._gated = lambda a, g: a
    elif name == "no_q_norm":
        n_q = sizes["num_attention_heads"]
        ref._rms = lambda x, w, eps: (
            x if x.ndim == 4 and x.shape[2] == n_q else rms(x, w, eps)
        )
    elif name == "fp8_experts":
        import jax

        fp8, swiglu, routed = shared.fp8, ref._swiglu, ref.routed

        def swiglu8(h, gate, up, down):
            h, gate, up, down = map(fp8, (h, gate, up, down))
            return fp8(jax.nn.silu(h @ gate) * (h @ up)) @ down

        def routed8(h, lp, sizes):
            ref._swiglu = swiglu8  # the experts' products only
            try:
                return routed(h, lp, sizes)
            finally:
                ref._swiglu = swiglu

        ref.routed = routed8
    elif name == "skip_expert":
        routed = ref.routed
        ref.routed = lambda h, lp, sizes: routed(
            h, dict(lp, e_down=shared.without_expert_0(lp["e_down"])), sizes
        )
    return undo


def fault_the_program(name: str, sizes) -> None:
    from torchdistx_tpu.models import afmoe
    from torchdistx_tpu.ops.pallas import flash_attention as fa

    if name == "window_causal":
        attention = afmoe.attention
        afmoe.attention = lambda *a, window=None, **kw: attention(*a, **kw)
    elif name == "edge_block":
        needs_mask = fa._needs_mask
        fa._needs_mask = lambda causal, q_start, k_start, bkv, s, bq=None, \
            window=None: needs_mask(causal, q_start, k_start, bkv, s)
    elif name == "rope_full":
        import dataclasses

        attn = afmoe._attn
        afmoe._attn = lambda x, lp, cfg, kind, **kw: attn(
            x, lp,
            cfg if kind == WINDOW else dataclasses.replace(cfg, window=1 << 30),
            WINDOW, **kw
        )
    elif name == "no_gate":
        afmoe._gated = lambda a, g: a
    elif name == "no_q_norm":
        llama, n_q = afmoe.llama_mod, sizes["num_attention_heads"]
        rms = llama._rmsnorm
        llama._rmsnorm = lambda x, w, eps: (
            x if x.ndim == 4 and x.shape[2] == n_q else rms(x, w, eps)
        )
    else:
        shared.fault_the_program(name)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.fault:
        sizes = bench.read_json(
            "configs", bench.read_json("workloads", f"{args.workload}.json")["config"] + ".json"
        )
        if args.rehearse:
            sizes = bench.merge(sizes, sizes.get("tiny", {}))
        fault_the_program(args.fault, sizes)
        return bench.main(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", "5", "--trace", "0"]
            + ["--rehearse"] * args.rehearse
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from drivers import train_steps_routed_queued as driver
    from drivers import train_steps_routed as routed

    global BLOCK
    device = bench.gate(1, args.rehearse)
    bench.open_compile_cache()
    cell = bench.load_cell(args.workload, 0, args.rehearse)
    tr = cell.config["training"]
    if args.rehearse:
        BLOCK = 16  # the tiny window is 48
    out = {"device": device, "tol": cell.config["tol"], "seeds": {}}

    def gradient(params, ids, variant, dtype):
        # A fault changes what the same static arguments trace to: a key
        # of its own keeps it out of the float32 reference's jit cache.
        sizes = dict(cell.config, _variant=1 + FAULTS.index(variant)) \
            if variant in FAULTS else cell.config
        undo = fault_the_reference(cell.ref, variant, cell.config) \
            if variant in FAULTS else (lambda: None)
        try:
            return driver.reference_gradient(
                params, ids[:, :-1], ids[:, 1:], ref=cell.ref,
                sizes=cell.check._freeze(sizes), dtype=jnp.dtype(dtype),
            )
        finally:
            undo()

    for seed in range(11, 11 + args.seeds):
        params = jax.jit(lambda k: cell.model.init_params(k, cell.cfg))(
            jax.random.PRNGKey(seed)
        )
        ids = np.random.default_rng(seed).integers(
            0, cell.config["vocab_size"], size=(tr["rows"], tr["seq"] + 1)
        ).astype(np.int32)
        g32 = gradient(params, ids, "f32", jnp.float32)
        rows = {}
        for variant in ("bf16",) + FAULTS:
            dtype = jnp.bfloat16 if variant in ("bf16", "fp8_experts") else jnp.float32
            reading = routed.compare(
                gradient(params, ids, variant, dtype), g32, ids[:, :-1]
            )
            ok, detail = routed.gradient_ok(cell, reading)
            rows[variant] = {
                "correct": ok, "gaps": reading["gaps"],
                "rows_percentiles_10_50_90": [
                    float(x) for x in np.percentile(reading["rows"], [10, 50, 90])
                ],
            }
            bench.say(f"seed {seed} {variant}: correct {ok}; {detail}")
        out["seeds"][seed] = rows
        del params, g32
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/tol_gradient_{cell.workload['config']}.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
