#!/usr/bin/env python3
"""The readings on either side of ``tol.gradient`` of a routed-expert
training cell (``drivers/train_steps_routed``); not run by the driver.

    python3 benchmarks/reference/measure_tol_gradient.py --workload <cell> [--seeds 2]
    python3 benchmarks/reference/measure_tol_gradient.py --workload <cell> --fault <name> [--seed n]

Without ``--fault``: the plain reference's gradient on the cell's shapes,
float32 ``highest``, against the SAME reference computed worse, each
through the driver's own comparison (``compare``, ``gradient_ok``):

* ``bf16``: bfloat16 at default precision, what rounding alone does (the
  program is expected to read about this);
* ``fp8_experts``, ``fp8_attention``: bfloat16, with the operands of the
  expert products, or q, k and v of attention, rounded to float8 e4m3's
  three mantissa bits (the nearest precision below the configuration's;
  values only, with exponent room to spare, and the cotangents stay as
  they are: the mildest form of it);
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

FAULTS = ("fp8_experts", "fp8_attention", "skip_expert")


def fp8(x):
    """``x`` with its values rounded to float8 e4m3's three mantissa bits;
    the gradient passes as if nothing had happened.  Five exponent bits, so
    that no tensor needs a scale (a maximum over the sorted rows would read
    the rows of absent experts, which the grouped product never wrote);
    ``reduce_precision``, because XLA removes a pair of converts that only
    loses precision."""
    import jax

    return x + jax.lax.stop_gradient(jax.lax.reduce_precision(x, 5, 3) - x)


def without_expert_0(e_down):
    import jax.numpy as jnp

    keep = jnp.arange(e_down.shape[0]) != 0
    return e_down * keep[:, None, None].astype(e_down.dtype)


def fault_the_reference(ref, name: str):
    """Put ``name`` into the reference module; returns the undo."""
    swiglu, attention, routed = ref._swiglu, ref.causal_attention, ref.routed

    def undo():
        ref._swiglu, ref.causal_attention, ref.routed = swiglu, attention, routed

    if name == "fp8_experts":
        import jax

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
    elif name == "fp8_attention":
        ref.causal_attention = lambda q, k, v: attention(fp8(q), fp8(k), fp8(v))
    elif name == "skip_expert":
        ref.routed = lambda h, lp, sizes: routed(
            h, dict(lp, e_down=without_expert_0(lp["e_down"])), sizes
        )
    return undo


def fault_the_program(name: str) -> None:
    import jax

    from torchdistx_tpu.models import deepseek_v3 as family

    if name == "fp8_experts":
        ragged_dot = jax.lax.ragged_dot
        jax.lax.ragged_dot = lambda x, w, sizes, **kw: ragged_dot(
            fp8(x), fp8(w), sizes, **kw
        )
    elif name == "fp8_attention":
        attention = family.attention
        family.attention = lambda q, k, v, **kw: attention(
            fp8(q), fp8(k), fp8(v), **kw
        )
    elif name == "skip_expert":
        routed = family.routed_experts
        family.routed_experts = lambda h, r, g, u, d, **kw: routed(
            h, r, g, u, without_expert_0(d), **kw
        )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.fault:
        fault_the_program(args.fault)
        return bench.main(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", "5", "--trace", "0"]
            + ["--rehearse"] * args.rehearse
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from drivers import train_steps_routed as driver

    device = bench.gate(1, args.rehearse)
    bench.open_compile_cache()
    cell = bench.load_cell(args.workload, 0, args.rehearse)
    tr = cell.config["training"]
    out = {"device": device, "tol": cell.config["tol"], "seeds": {}}

    def gradient(params, ids, variant, dtype):
        # A fault changes what the same static arguments trace to: a key
        # of its own keeps it out of the float32 reference's jit cache.
        sizes = dict(cell.config, _variant=1 + FAULTS.index(variant)) \
            if variant in FAULTS else cell.config
        undo = fault_the_reference(cell.ref, variant)
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
        for variant, dtype in (
            ("bf16", jnp.bfloat16), ("fp8_experts", jnp.bfloat16),
            ("fp8_attention", jnp.bfloat16), ("skip_expert", jnp.float32),
        ):
            reading = driver.compare(
                gradient(params, ids, variant, dtype), g32, ids[:, :-1]
            )
            ok, detail = driver.gradient_ok(cell, reading)
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
