#!/usr/bin/env python3
"""The readings on either side of ``tol.gradient`` and ``tol.gradient_rows``
of the Olmo-Hybrid training cell (``drivers/train_steps_olmo_hybrid``);
not run by ``run.py``.

    python3 benchmarks/reference/measure_tol_gradient_olmo_hybrid.py --workload <cell> [--seed 11] [--only a,b] [--rehearse]
    python3 benchmarks/reference/measure_tol_gradient_olmo_hybrid.py --workload <cell> --fault <name> [--seed n] [--rehearse]

Without ``--fault``, in one process: the float32 ``highest`` reference's
gradient on the cell's shapes, then each variant below (the program's
first) through the cell's own comparison (``train_steps_ssm.compare``
with the rows by position in a delta-rule chunk,
``train_steps_routed.gradient_ok``):

* the REFERENCE computed worse or with a fault put in: ``bf16``
  (bfloat16 at default precision, what rounding alone does: the program
  is expected to read about this), ``fp8`` (bfloat16 with q, k and v of
  both mixers rounded to float8 e4m3's three mantissa bits, the nearest
  precision below the configuration's: has to come out NOT correct, as
  every fault), and each of ``FAULTS``;
* the PROGRAM, each of ``FAULTS`` put into it: one step of the timed
  train step from the same parameters and a fresh AdamW state, its
  gradient read back from the first moment (``mu / (1 - b1)``) and held to
  the same float32 reference, its loss to the reference's (``tol.loss``).

``FAULTS``: ``beta_no_factor`` (beta = sigmoid, not 2 sigmoid),
``k_no_l2norm`` (k not normalised), ``g_dropped`` (no decay),
``chunk_reset`` (the state not carried across a boundary of the delta
rule's chunks), ``no_gate`` (the gated norm without ``silu(z)``),
``qk_norm_per_head`` (the full layer's q and k normed per head, not over
the whole projection).  Parameters come from the family's ``init_params``
(bfloat16), not the paper's path.

The readings go to ``chiprun_out/tol_gradient_olmo-hybrid-7b.json`` and,
one line each, to standard output.

With ``--fault``: one run of the whole harness (``run.py``'s ``main``,
``--seconds 5``) with that fault put into the program, which has to print
``"correct": false``.
"""

import argparse
import dataclasses
import inspect
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402
from reference import measure_tol_gradient as shared  # noqa: E402

FAULTS = (
    "beta_no_factor", "k_no_l2norm", "g_dropped", "chunk_reset", "no_gate",
    "qk_norm_per_head",
)
VARIANTS = ("bf16", "fp8") + FAULTS


def _chunks_apart(rule, chunk):
    """``rule`` over each time chunk of ``chunk`` positions apart, every
    chunk from a zero state: the chunks folded into the batch."""
    import jax.numpy as jnp

    def apart(q, k, v, g, beta, *args, **kw):
        b, t = q.shape[:2]
        pad = -t % chunk
        xs = [
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        ]
        folded = [x.reshape(b * (t + pad) // chunk, chunk, *x.shape[2:]) for x in xs]
        o = rule(*folded, *args, **kw)
        return o.reshape(b, t + pad, *o.shape[2:])[:, :t]

    return apart


def _reset_at_chunks(g, chunk):
    """``g (B, T, H)`` with the gate at each chunk's first position so low
    that ``exp`` of it is 0 in float32: the recurrence's state is zeroed
    there, one position at a time, at no cost in memory (the program's
    fault folds the chunks into the batch instead, ``_chunks_apart``)."""
    import jax.numpy as jnp

    first = (jnp.arange(g.shape[1]) % chunk == 0)[None, :, None]
    return jnp.where(first, -1e4, g)


def _per_head(norm, heads):
    """q and k each RMS-normed per head of ``heads``, then the whole
    projection's weight."""
    def qk(q, k, weights, eps):
        def one(x, w):
            per = x.reshape(*x.shape[:-1], heads, -1)
            return (norm(per, 1.0, eps).reshape(x.shape) * w).astype(x.dtype)

        return one(q, weights[0]), one(k, weights[1])

    return qk


def fault_the_reference(ref, name: str, sizes: dict):
    """Put ``name`` into the reference module; returns the undo."""
    keys = ("BETA_SCALE", "_qk", "_gates", "_delta", "_out_gate", "_qk_norm",
            "_causal")
    saved = {k: getattr(ref, k) for k in keys}

    def undo():
        for k, v in saved.items():
            setattr(ref, k, v)

    qk, gates, delta, causal = ref._qk, ref._gates, ref._delta, ref._causal
    if name == "fp8":
        fp8 = shared.fp8
        ref._delta = lambda q, k, v, g, beta, s: delta(fp8(q), fp8(k), fp8(v), g, beta, s)
        ref._causal = lambda q, k, v: causal(fp8(q), fp8(k), fp8(v))
    elif name == "beta_no_factor":
        ref.BETA_SCALE = 1.0
    elif name == "k_no_l2norm":
        ref._qk = lambda q, k, d_k: (qk(q, k, d_k)[0], k)
    elif name == "g_dropped":
        ref._gates = lambda x, lp: (gates(x, lp)[0], 0.0 * gates(x, lp)[1])
    elif name == "chunk_reset":
        chunk = sizes["training"]["delta_chunk"]
        ref._delta = lambda q, k, v, g, beta, s: delta(
            q, k, v, _reset_at_chunks(g, chunk), beta, s
        )
    elif name == "no_gate":
        ref._out_gate = lambda o, z: o
    elif name == "qk_norm_per_head":
        per_head = _per_head(ref._rms, sizes["num_attention_heads"])
        ref._qk_norm = lambda q, k, lp, s: per_head(
            q, k, (lp["q_norm"], lp["k_norm"]), s["rms_norm_eps"]
        )
    return undo


def fault_the_program(name: str, cfg):
    """Put ``name`` into the program (``models/olmo_hybrid.py``); returns
    ``(cfg, undo)``."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import llama as llama_mod
    from torchdistx_tpu.models import olmo_hybrid as model

    keys = ("_qk_l2norm", "gated_delta_rule", "_out_gate", "_qk_norm")
    saved = {k: getattr(model, k) for k in keys}

    def undo():
        for k, v in saved.items():
            setattr(model, k, v)

    qk, rule = model._qk_l2norm, model.gated_delta_rule
    if name == "beta_no_factor":
        cfg = dataclasses.replace(cfg, allow_neg_eigval=False)
    elif name == "k_no_l2norm":
        model._qk_l2norm = lambda q, k: (qk(q, k)[0], k)
    elif name == "g_dropped":
        model.gated_delta_rule = lambda q, k, v, g, beta, **kw: rule(
            q, k, v, 0.0 * g, beta, **kw
        )
    elif name == "chunk_reset":
        model.gated_delta_rule = _chunks_apart(rule, cfg.delta_chunk)
    elif name == "no_gate":
        model._out_gate = lambda o, z: o
    elif name == "qk_norm_per_head":
        def rms(x, w, eps):
            return llama_mod._rmsnorm(x, jnp.ones(x.shape[-1:], x.dtype), eps)

        per_head = _per_head(rms, cfg.n_heads)
        model._qk_norm = lambda q, k, lp, eps: per_head(
            q, k, (lp["q_norm"], lp["k_norm"]), eps
        )
    return cfg, undo


def _program_reading(cell, params, tokens, targets, clean, name):
    """One step of the train step with ``name`` in the program, from
    ``params`` and a fresh AdamW state: ``(gradient reading, loss)``."""
    import jax
    import jax.numpy as jnp
    import optax

    from drivers import train_steps_olmo_hybrid as driver
    from drivers import train_steps_routed as routed
    from drivers import train_steps_ssm as ssm
    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    tr = cell.config["training"]
    cfg, undo = fault_the_program(name, cell.cfg)
    try:
        mesh = make_mesh(MeshSpec(fsdp=1), devices=jax.devices()[:1])
        tx = getattr(optax, tr["optimizer"])(tr["lr"])
        b1 = inspect.signature(getattr(optax, tr["optimizer"])).parameters["b1"].default
        _, step_fn = ts.make_train_step(
            cfg, mesh, tx, model=cell.model, attn_impl=tr["attn_impl"]
        )
        state = ts.TrainState(
            params=jax.device_put(params), opt_state=jax.jit(tx.init)(params),
            step=jnp.zeros((), jnp.int32),
        )
        batch = jax.device_put(
            {"tokens": tokens, "targets": targets}, ts.batch_sharding(mesh)
        )
        state, metrics = step_fn(state, batch)
        g = jax.tree.map(
            lambda m: m.astype(jnp.float32) / (1 - b1),
            driver.compared(routed._first_moment(state.opt_state)),
        )
        loss = float(metrics["loss"])
        reading = ssm.compare(g, jax.device_put(clean), tokens, cfg.delta_chunk)
        del state, g
        return reading, loss
    finally:
        undo()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.fault:
        fam = __import__("families.olmo_hybrid", fromlist=["native"])
        native = fam.native

        def faulted(sizes, dtype):
            mod, cfg = native(sizes, dtype)
            return mod, fault_the_program(args.fault, cfg)[0]

        fam.native = faulted
        return bench.main(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", "5", "--trace", "0"]
            + ["--rehearse"] * args.rehearse
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from drivers import train_steps_olmo_hybrid as driver
    from drivers import train_steps_routed as routed
    from drivers import train_steps_ssm as ssm

    device = bench.gate(1, args.rehearse)
    bench.open_compile_cache()
    cell = bench.load_cell(args.workload, args.seed, args.rehearse)
    tr, ref, freeze = cell.config["training"], cell.ref, cell.check._freeze
    chunk = cell.cfg.delta_chunk
    only = set(args.only.split(",")) - {""}
    out = {"device": device, "tol": cell.config["tol"], "seed": args.seed}

    params = jax.device_get(jax.jit(lambda k: cell.model.init_params(k, cell.cfg))(
        jax.random.PRNGKey(args.seed)
    ))
    ids = np.random.default_rng(args.seed).integers(
        0, cell.config["vocab_size"], size=(tr["rows"], tr["seq"] + 1)
    ).astype(np.int32)
    tokens, targets = ids[:, :-1], ids[:, 1:]

    def gradient(name, dtype):
        # A fault changes what the same static arguments trace to: a key
        # of its own keeps it out of the float32 reference's jit cache.
        sizes = dict(cell.config, _variant=1 + VARIANTS.index(name)) \
            if name in VARIANTS else cell.config
        undo = fault_the_reference(ref, name, cell.config)
        try:
            return jax.device_get(driver.reference_gradient(
                params, tokens, targets, ref=ref, sizes=freeze(sizes),
                dtype=jnp.dtype(dtype),
            ))
        finally:
            undo()

    t0 = time.perf_counter()
    clean = gradient("f32", jnp.float32)
    ref_loss = cell.check.loss(ref, params, cell.config, tokens, targets)
    bench.say(f"float32 reference: loss {ref_loss:.5f} ({time.perf_counter() - t0:.0f} s)")

    def record(key, reading, loss=None):
        ok, detail = routed.gradient_ok(cell, reading)
        gaps = reading["gaps"]
        groups = {k: v for k, v in gaps.items() if k.startswith("embed rows")}
        leaves = {k: v for k, v in gaps.items() if k not in groups}
        worst = max(leaves, key=lambda k: np.nan_to_num(leaves[k], nan=np.inf))
        row = {
            "correct": bool(ok), "worst_leaf": [worst, float(leaves[worst])],
            "worst_group": max(groups.values()) if groups else None,
            "rows_median": float(np.median(reading["rows"])), "gaps": gaps,
        }
        if loss is not None:
            row["loss_gap"] = abs(loss - ref_loss)
            row["correct"] = bool(ok and row["loss_gap"] <= cell.config["tol"]["loss"])
        out[key] = row
        bench.say(
            f"{key}: correct {row['correct']}; worst leaf {leaves[worst]:.4f} at "
            f"{worst}; worst row group {row['worst_group']:.4f}; rows median "
            f"{row['rows_median']:.5f}"
            + (f"; loss off by {row['loss_gap']:.5f}" if loss is not None else "")
            + f" ({time.perf_counter() - t0:.0f} s)"
        )

    for name in ("clean",) + FAULTS:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        reading, loss = _program_reading(cell, params, tokens, targets, clean, name)
        record(f"program {name}", reading, loss)
    for name in VARIANTS:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        dtype = jnp.bfloat16 if name in ("bf16", "fp8") else jnp.float32
        record(f"reference {name}", ssm.compare(
            jax.device_put(gradient(name, dtype)), jax.device_put(clean), tokens, chunk
        ))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/tol_gradient_{cell.workload['config']}.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
