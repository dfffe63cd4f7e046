#!/usr/bin/env python3
"""The readings on either side of ``tol.select``, ``tol.gradient_rows`` and
``tol.gradient`` of the MiniCPM-SALA training cell
(``drivers/train_steps_sala``); not run by the driver.

    python3 benchmarks/reference/measure_tol_gradient_sala.py --workload <cell> [--seeds 1] [--only a,b]

The reference at the cell's shapes plays the program: each variant below
is the plain reference computed worse, or with a fault put in, and goes
through the driver's own three comparisons against the float32 ``highest``
reference: its choice of layer 0's blocks against the reference's
(``choice_gaps``: the farthest differing block from its row's 64th score),
and its gradient under its OWN choice against the float32 reference's
under that same choice, as the driver takes the program's
(``compare``, ``gradient_ok``).  Parameters come from the family's
``init_params`` (bfloat16, the program's init), not the paper's path.

* ``bf16``: bfloat16 at default precision, what rounding alone does (the
  program is expected to read about this);
* ``fp8``: bfloat16 with q, k and v of both mixers rounded to float8
  e4m3's three mantissa bits, the nearest precision below the
  configuration's (``measure_tol_gradient.fp8``): has to come out NOT
  correct, as every fault below;
* ``state_bf16``: the lightning layers as a recurrence in chunks of 64
  whose state is rounded to bfloat16 at every chunk;
* ``no_decay``: lambda = 1 in every head;
* ``minimax_decay``: MiniMax-01's per-layer factor on the slopes, ``1 -
  layer / (32 - 1) + 1e-5``;
* ``rope_sparse``: rope on the sparse layer's q and k (choice and
  attention);
* ``no_rope_lightning``: no rope on the lightning layers;
* ``local_not_forced``: the local window's blocks compete like any other;
* ``per_head``: each query head chooses its own blocks (its reading of the
  choice is the first head of each group);
* ``no_scale_emb``, ``no_depth_scale``, ``no_logit_divisor``: one of the
  three muP scales left out;
* ``no_output_norm``: the lightning output's RMS norm left out.

The readings go to ``chiprun_out/tol_gradient_minicpm-sala.json`` and, one
line each, to standard output: what ``tol.note`` is written from.
"""

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402
from reference import measure_tol_gradient as shared  # noqa: E402

FAULTS = (
    "state_bf16", "no_decay", "minimax_decay", "rope_sparse",
    "no_rope_lightning", "local_not_forced", "per_head", "no_scale_emb",
    "no_depth_scale", "no_logit_divisor", "no_output_norm",
)
VARIANTS = ("bf16", "fp8") + FAULTS
STATE_CHUNK = 64


def _lightning_state_bf16(ref):
    """The lightning mixer as a recurrence over chunks of ``STATE_CHUNK``,
    the state carried in bfloat16 (float32 everywhere else)."""
    import jax
    import jax.numpy as jnp

    def lightning(q, k, v, sizes, layer):
        b, t, h, d = q.shape
        c = min(STATE_CHUNK, t)
        a = ref.lightning_decays(sizes, layer)
        i = jnp.arange(c, dtype=jnp.float32)
        diff = i[:, None] - i[None, :]
        mask = jnp.where(diff >= 0, jnp.exp(a[:, None, None] * jnp.maximum(diff, 0.0)), 0.0)
        qs = q / math.sqrt(d)

        @jax.checkpoint
        def chunk(s, xs):
            qc, kc, vc = xs  # (B, C, H, D)
            o = jnp.einsum("bihd,bjhd->bhij", qc, kc) * mask
            o = jnp.einsum("bhij,bjhd->bihd", o, vc)
            o = o + jnp.einsum(
                "bihd,bhde->bihe",
                qc * jnp.exp(a[None, :] * (i[:, None] + 1.0))[None, :, :, None], s,
            )
            kd = kc * jnp.exp(a[None, :] * (c - 1.0 - i[:, None]))[None, :, :, None]
            s = s * jnp.exp(a * c)[None, :, None, None] + jnp.einsum("bjhd,bjhe->bhde", kd, vc)
            return s.astype(jnp.bfloat16).astype(s.dtype), o

        xs = tuple(
            x.reshape(b, t // c, c, h, d).swapaxes(0, 1) for x in (qs, k, v)
        )
        _, o = jax.lax.scan(chunk, jnp.zeros((b, h, d, d), q.dtype), xs)
        return o.swapaxes(0, 1).reshape(b, t, h * d)

    return lightning


def fault_the_reference(ref, name: str):
    """Put ``name`` into the reference module; returns the undo."""
    keys = (
        "_sparse", "_lightning", "lightning_decays", "_positions",
        "forced_blocks", "choice_scores", "_o_norm",
    )
    saved = {k: getattr(ref, k) for k in keys}

    def undo():
        for k, v in saved.items():
            setattr(ref, k, v)

    sparse, lightning, decays, positions = (
        ref._sparse, ref._lightning, ref.lightning_decays, ref._positions
    )
    if name == "fp8":
        fp8 = shared.fp8
        ref._sparse = lambda q, k, v, sizes, chosen=None: sparse(
            fp8(q), fp8(k), fp8(v), sizes, chosen
        )
        ref._lightning = lambda q, k, v, sizes, layer: lightning(
            fp8(q), fp8(k), fp8(v), sizes, layer
        )
    elif name == "state_bf16":
        ref._lightning = _lightning_state_bf16(ref)
    elif name == "no_decay":
        ref.lightning_decays = lambda sizes, layer: 0.0 * decays(sizes, layer)
    elif name == "minimax_decay":
        ref.lightning_decays = lambda sizes, layer: decays(sizes, layer) * (
            1.0 - layer / (sizes["depth_scale_layers"] - 1) + 1e-5
        )
    elif name == "rope_sparse":
        ref._positions = lambda q, k, sizes, kind: positions(
            q, k, sizes, ref.LIGHTNING
        )
    elif name == "no_rope_lightning":
        ref._positions = lambda q, k, sizes, kind: (q, k)
    elif name == "local_not_forced":
        forced = ref.forced_blocks

        def init_only(rows, n_blocks, sizes):
            return forced(rows, n_blocks, dict(sizes, sparse_window_size=-(1 << 30)))

        ref.forced_blocks = init_only
    elif name == "per_head":
        import jax.numpy as jnp

        choice = ref.choice_scores

        def each_head(k, q):
            return jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)

        ref._sparse = lambda q, k, v, sizes, chosen=None: sparse(
            q, each_head(k, q), each_head(v, q), sizes, None
        )

        def first_heads(q, k, sizes, rows):
            g = q.shape[2] // k.shape[2]
            chosen, ranked = choice(q, each_head(k, q), sizes, rows)
            return chosen[:, ::g], ranked[:, ::g]

        ref.choice_scores = first_heads
    elif name == "no_output_norm":
        ref._o_norm = lambda a, w, eps: a
    return undo


def variant_sizes(config, name):
    """The sizes a variant is traced with: a key of its own keeps it out of
    the jit caches of the others; the muP faults are sizes."""
    sizes = dict(config, _variant=1 + VARIANTS.index(name))
    if name == "no_scale_emb":
        sizes["scale_emb"] = 1
    elif name == "no_depth_scale":
        sizes["scale_depth"] = math.sqrt(sizes["depth_scale_layers"])
    elif name == "no_logit_divisor":
        sizes["dim_model_base"] = sizes["hidden_size"]
    return sizes


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from drivers import train_steps_routed as routed
    from drivers import train_steps_sala as driver

    device = bench.gate(1, args.rehearse)
    bench.open_compile_cache()
    cell = bench.load_cell(args.workload, 0, args.rehearse)
    tr, ref, freeze = cell.config["training"], cell.ref, cell.check._freeze
    topk = cell.config["sparse_topk"]
    variants = [v for v in VARIANTS if not args.only or v in args.only.split(",")]
    out = {"device": device, "tol": cell.config["tol"], "seeds": {}}

    for seed in range(11, 11 + args.seeds):
        params = jax.jit(lambda k: cell.model.init_params(k, cell.cfg))(
            jax.random.PRNGKey(seed)
        )
        ids = np.random.default_rng(seed).integers(
            0, cell.config["vocab_size"], size=(tr["rows"], tr["seq"] + 1)
        ).astype(np.int32)
        tokens, targets = ids[:, :-1], ids[:, 1:]
        f32 = jnp.dtype(jnp.float32)
        chosen, ranked = driver.reference_choice(
            params, tokens, ref=ref, sizes=freeze(cell.config)
        )
        # The clean gradient under the reference's own choice waits on the
        # host; one under another choice lives for its variant alone (at
        # the cell's size each is 2.4 GB of float32).
        own = None

        def clean_gradient(choice):
            return driver.reference_gradient(
                params, tokens, targets, choice, ref=ref,
                sizes=freeze(cell.config), dtype=f32,
            )

        rows = {}
        for name in variants:
            t0 = time.perf_counter()
            dtype = jnp.dtype(jnp.bfloat16 if name in ("bf16", "fp8") else jnp.float32)
            sizes = freeze(variant_sizes(cell.config, name))
            undo = fault_the_reference(ref, name)
            try:
                mine, _ = driver.reference_choice(
                    params, tokens, ref=ref, sizes=sizes, dtype=dtype
                )
                g = driver.reference_gradient(
                    params, tokens, targets, mine, ref=ref, sizes=sizes, dtype=dtype,
                )
            finally:
                undo()
            choice = driver.choice_gaps(mine, chosen, ranked, topk)
            if choice["blocks_differ"]:
                clean = clean_gradient(mine)
            else:
                if own is None:
                    own = jax.device_get(clean_gradient(mine))
                clean = jax.device_put(own)
            reading = routed.compare(g, clean, tokens)
            grad_ok, detail = routed.gradient_ok(cell, reading)
            sel_ok, sel_detail = driver.choice_ok(cell, choice)
            leaf = max(reading["gaps"], key=lambda k: np.nan_to_num(reading["gaps"][k], nan=np.inf))
            rows[name] = {
                "correct": bool(grad_ok and sel_ok), "choice": choice,
                "worst_leaf": [leaf, float(reading["gaps"][leaf])],
                "rows_median": float(np.median(reading["rows"])),
                "gaps": reading["gaps"],
            }
            bench.say(
                f"seed {seed} {name}: correct {grad_ok and sel_ok}; select "
                f"{choice['select']:.4g} ({choice['rows_differ']} rows differ); "
                f"rows median {rows[name]['rows_median']:.5f}; worst leaf "
                f"{reading['gaps'][leaf]:.4f} at {leaf} "
                f"({time.perf_counter() - t0:.0f} s)"
            )
            del g, clean, mine
        out["seeds"][seed] = rows
        del params, own
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/tol_gradient_{cell.workload['config']}.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
