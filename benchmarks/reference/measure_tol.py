#!/usr/bin/env python3
"""Measure bf16's own floor for a configuration; not run by the driver.

    python3 benchmarks/reference/measure_tol.py --workload <cell> [--streams 4]

Run once, on the chip, when a configuration is defined.  The SAME plain
forward as the reference, in bfloat16 at default precision, is compared with
the float32 reference on the shapes the cell checks:

* serving: random streams as long as the serving block's ``max_model_len``;
  at every position the bf16 forward's argmax token is looked up in the f32
  logits, and its distance below the f32 best is that position's gap.  The
  worst gap over all positions is the floor: what rounding alone does at
  this depth, width and context.
* training (if the configuration has a training block): the two losses on
  fresh batches; the worst difference is the floor.

The tolerance written into ``configs/<config>.json`` is twice the floor.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = bench.gate(1, args.rehearse)
    bench.open_compile_cache()
    cell = bench.load_cell(args.workload, 0, args.rehearse)
    params = bench.make_params(cell)
    rng = np.random.default_rng(0)
    vocab, out = cell.config["vocab_size"], {"device": device}
    chk = cell.check

    if "serving" in cell.config:
        width = cell.config["serving"]["max_model_len"]
        gaps = []
        for _ in range(args.streams):
            seq = rng.integers(0, vocab, size=width).astype(np.int32)
            f32, b16 = (
                chk.stream_logits(
                    cell.ref, params, cell.config, seq[:1], seq[1:], width,
                    width, dtype,
                )
                for dtype in (jnp.float32, jnp.bfloat16)
            )
            pick = b16.argmax(-1)
            gaps.append(f32.max(-1) - f32[np.arange(len(pick)), pick])
        gaps = np.concatenate(gaps)
        out["logit_gap"] = {
            "positions": int(gaps.size), "width": width,
            "floor": float(gaps.max()), "p99": float(np.quantile(gaps, 0.99)),
            "argmax_differs_at": int((gaps > 0).sum()),
            "max_abs_logit": float(np.abs(f32).max()),
            "tol": 2 * float(gaps.max()),
        }
    if "training" in cell.config:
        tr = cell.config["training"]
        diffs = []
        for _ in range(args.batches):
            ids = rng.integers(
                0, vocab, size=(tr["rows"], tr["seq"] + 1)
            ).astype(np.int32)
            a, b = (
                chk.loss(
                    cell.ref, params, cell.config, ids[:, :-1], ids[:, 1:], d
                )
                for d in (jnp.float32, jnp.bfloat16)
            )
            diffs.append(abs(a - b))
        out["loss"] = {
            "batches": args.batches, "floor": max(diffs),
            "all": diffs, "tol": 2 * max(diffs),
        }
    print(json.dumps(out, indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/tol_{cell.workload['config']}.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
