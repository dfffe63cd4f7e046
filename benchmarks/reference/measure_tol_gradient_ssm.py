#!/usr/bin/env python3
"""The readings on either side of ``tol.gradient_rows`` and
``tol.gradient`` of a state-space training cell
(``drivers/train_steps_ssm``); ``measure_tol_gradient.py``'s method, not
run by the driver.

    python3 benchmarks/reference/measure_tol_gradient_ssm.py --workload <cell> [--seeds 2]
    python3 benchmarks/reference/measure_tol_gradient_ssm.py --workload <cell> --fault <name> [--seed n]

Without ``--fault``: the plain reference's gradient on the cell's shapes,
float32 ``highest``, against the SAME reference computed worse, each
through the driver's own comparison (``compare``, ``gradient_ok``):

* ``bf16``: bfloat16 at default precision, the scan's state float32 as the
  configuration states: what rounding alone does (the program is expected
  to read about this);
* ``bf16_state``: that, with the scan's state rounded to bfloat16 after
  every position (the nearest precision below the configuration's for the
  one thing it keeps float32);
* ``fp8_mixer``: bfloat16, with both operands of the mixer's four products
  (``W_in``, ``W_x``, ``W_dt``, ``W_out``) rounded to float8 e4m3's three
  mantissa bits (values only; the cotangents stay as they are);
* ``chunk_reset``: float32, the state zeroed at every chunk boundary;
* ``conv_tap``: float32, the convolution without its oldest tap;
* ``no_skip``: float32, without the ``D * u`` skip.

The first three are for the precision limit (the median row gap), the
last three for the structure limit (the worst leaf or group of rows).
Every control has to come out NOT correct.  Parameters come from the
family's ``init_params`` (the same fills as the paper's path).

With ``--fault`` (``fp8_mixer``, ``chunk_reset``, ``conv_tap``,
``no_skip``): one run of the whole harness (``run.py``'s ``main``,
``--seconds 5``) with that fault put into the PROGRAM, around its own
kernels, which has to print ``"correct": false``.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402

from reference.measure_tol_gradient import fp8  # noqa: E402

FAULTS = ("bf16_state", "fp8_mixer", "chunk_reset", "conv_tap", "no_skip")
PROGRAM_FAULTS = FAULTS[1:]


def faulty_scan(ref, *, state_dtype=None, reset_every=None, skip=True):
    """``ref.scan`` with one thing wrong."""
    import jax
    import jax.numpy as jnp

    def scan(u, delta, a, b, c, d):
        u, delta, b, c = (x.astype(jnp.float32) for x in (u, delta, b, c))
        bsz, _, ch = u.shape

        def step(h, x):
            i, u_t, dt_t, b_t, c_t = x
            if reset_every:
                h = jnp.where(i % reset_every == 0, 0.0, h)
            h = (
                jnp.exp(dt_t[..., None] * a) * h
                + (dt_t * u_t)[..., None] * b_t[:, None, :]
            )
            if state_dtype is not None:
                h = h.astype(state_dtype).astype(jnp.float32)
            return h, (h * c_t[:, None, :]).sum(-1)

        @jax.checkpoint
        def stretch(h, xs):
            return jax.lax.scan(step, h, xs)

        size = ref.SCAN_BLOCK
        t = u.shape[1]
        size = min(size, t)
        if t % size:
            raise ValueError(f"{t} positions are no whole stretches of {size}")
        xs = (jnp.arange(t),) + tuple(
            x.swapaxes(0, 1) for x in (u, delta, b, c)
        )
        h0 = jnp.zeros((bsz, ch, a.shape[1]), jnp.float32)
        _, y = jax.lax.scan(
            stretch, h0, tuple(x.reshape(-1, size, *x.shape[1:]) for x in xs)
        )
        y = y.reshape(t, bsz, ch).swapaxes(0, 1)
        return y + d.astype(jnp.float32) * u if skip else y

    return scan


def fp8_mamba(ref):
    """``ref.mamba`` with both operands of its four products in float8."""
    import jax
    import jax.numpy as jnp

    def dot(x, w):
        return fp8(x) @ fp8(w)

    def mamba(h, lp, sizes):
        eps, n, r = (
            sizes["rms_norm_eps"], sizes["mamba_d_state"], sizes["mamba_dt_rank"]
        )
        u, z = jnp.split(dot(h, lp["w_in"]), 2, axis=-1)
        u = jax.nn.silu(ref.conv(u, lp["conv_w"], lp["conv_b"]))
        p = dot(u, lp["w_x"])
        dt = ref._rms(p[..., :r], lp["dt_norm"], eps)
        b = ref._rms(p[..., r:r + n], lp["b_norm"], eps)
        c = ref._rms(p[..., r + n:], lp["c_norm"], eps)
        delta = jax.nn.softplus(dot(dt, lp["w_dt"]) + lp["b_dt"])
        a = -jnp.exp(lp["a_log"].astype(jnp.float32))
        y = ref.scan(u, delta, a, b, c, lp["d"]).astype(h.dtype)
        return dot(y * jax.nn.silu(z), lp["w_out"])

    return mamba


def without_oldest_tap(w):
    """Taps ``(K, C)`` (or stacked): tap 0 meets the oldest position."""
    import jax.numpy as jnp

    keep = (jnp.arange(w.shape[-2]) != 0).astype(w.dtype)
    return w * keep[:, None]


def fault_the_reference(ref, name: str, chunk: int):
    """Put ``name`` into the reference module; returns the undo."""
    scan, conv, mamba = ref.scan, ref.conv, ref.mamba

    def undo():
        ref.scan, ref.conv, ref.mamba = scan, conv, mamba

    import jax.numpy as jnp

    if name == "bf16_state":
        ref.scan = faulty_scan(ref, state_dtype=jnp.bfloat16)
    elif name == "fp8_mixer":
        ref.mamba = fp8_mamba(ref)
    elif name == "chunk_reset":
        ref.scan = faulty_scan(ref, reset_every=chunk)
    elif name == "conv_tap":
        ref.conv = lambda u, w, b: conv(u, without_oldest_tap(w), b)
    elif name == "no_skip":
        ref.scan = faulty_scan(ref, skip=False)
    return undo


def fault_the_program(name: str) -> None:
    """Around the program's own kernels: what they are given, or what
    becomes of what they return."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import jamba as family

    scan, conv, mamba = family.selective_scan, family._conv, family._mamba
    if name == "chunk_reset":
        # every chunk a row of its own: the kernels run, no state crosses
        def reset(u, delta, a, b, c, d, *, chunk, **kw):
            bsz, t, ch = u.shape
            n = -(-t // chunk)

            def cut(x):  # zero rows to a whole chunk, as the scan pads
                x = jnp.pad(x, ((0, 0), (0, n * chunk - t), (0, 0)))
                return x.reshape(bsz * n, chunk, x.shape[-1])

            y = scan(cut(u), cut(delta), a, cut(b), cut(c), d, chunk=chunk, **kw)
            return y.reshape(bsz, n * chunk, ch)[:, :t]

        family.selective_scan = reset
    elif name == "conv_tap":
        family._conv = lambda u, w, b: conv(u, without_oldest_tap(w), b)
    elif name == "no_skip":
        family.selective_scan = lambda u, delta, a, b, c, d, **kw: scan(
            u, delta, a, b, c, jnp.zeros_like(d), **kw
        )
    elif name == "fp8_mixer":
        # the four matrices and the mixer's input (the products' other
        # operands are made inside the mixer)
        family._mamba = lambda h, lp, cfg, mesh: mamba(
            fp8(h),
            dict(lp, **{k: fp8(lp[k]) for k in ("w_in", "w_x", "w_dt", "w_out")}),
            cfg, mesh,
        )
    else:
        raise SystemExit(f"no program fault {name!r}: one of {PROGRAM_FAULTS}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--fault", choices=PROGRAM_FAULTS)
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

    from drivers import train_steps_routed as routed
    from drivers import train_steps_ssm as driver

    device = bench.gate(1, args.rehearse)
    bench.open_compile_cache()
    cell = bench.load_cell(args.workload, 0, args.rehearse)
    tr, chunk = cell.config["training"], cell.cfg.scan_chunk
    out = {"device": device, "tol": cell.config["tol"], "seeds": {}}

    def gradient(params, ids, variant, dtype):
        # A fault changes what the same static arguments trace to: a key
        # of its own keeps it out of the float32 reference's jit cache.
        sizes = dict(cell.config, _variant=1 + FAULTS.index(variant)) \
            if variant in FAULTS else cell.config
        undo = fault_the_reference(cell.ref, variant, chunk)
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
            ("bf16", jnp.bfloat16), ("bf16_state", jnp.bfloat16),
            ("fp8_mixer", jnp.bfloat16), ("chunk_reset", jnp.float32),
            ("conv_tap", jnp.float32), ("no_skip", jnp.float32),
        ):
            reading = driver.compare(
                gradient(params, ids, variant, dtype), g32, ids[:, :-1], chunk
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
