"""Training steps for ``--seconds``: fresh batches made from the seed on
the host while the previous step runs, a fixed amount of work per step, the
window closed by ``block_until_ready`` on the last step's loss.

Weights come from the paper's path (``run.make_params``), never from
``init_fn``; the optimizer state is built around them.  The reference's loss
for the first measured batch is taken just before the window, from the
parameters as they then stand: the step donates its state, so afterwards
they no longer exist.  That forward is part of set-up.
"""

import time

import numpy as np


def build(cell, params):
    import jax
    import jax.numpy as jnp
    import optax

    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    tr = cell.config["training"]
    mesh = make_mesh(MeshSpec(fsdp=cell.chips), devices=jax.devices()[: cell.chips])
    tx = getattr(optax, tr["optimizer"])(tr["lr"])
    _, step_fn = ts.make_train_step(
        cell.cfg, mesh, tx, model=cell.model, attn_impl=tr["attn_impl"]
    )
    state = ts.TrainState(
        params=params, opt_state=jax.jit(tx.init)(params),
        step=jnp.zeros((), jnp.int32),
    )
    st = {
        "state": state, "step_fn": step_fn,
        "sharding": ts.batch_sharding(mesh),
        "rng": np.random.default_rng(cell.seed),
    }
    # The first step compiles; the warm steps settle the shardings the
    # step hands back to itself.
    warm = [
        _step(st, _batch(cell, st))["loss"]
        for _ in range(1 + cell.workload["traffic"]["warm_steps"])
    ]
    st["warm_losses"] = [float(x) for x in warm]
    return st


def _batch(cell, st):
    import jax

    tr = cell.config["training"]
    ids = st["rng"].integers(
        0, cell.config["vocab_size"], size=(tr["rows"] * cell.chips, tr["seq"] + 1)
    ).astype(np.int32)
    host = {"tokens": ids[:, :-1], "targets": ids[:, 1:]}
    return host, jax.device_put(host, st["sharding"])


def _step(st, batch):
    st["state"], metrics = st["step_fn"](st["state"], batch[1])
    return metrics


def run(cell, st, seconds, tracer):
    import jax

    from torchdistx_tpu import telemetry

    run_start = time.perf_counter()
    batch = _batch(cell, st)
    ref_loss = cell.check.loss(
        cell.ref, st["state"].params, cell.config, batch[0]["tokens"],
        batch[0]["targets"],
    )
    tr = cell.config["training"]
    counters0 = telemetry.counters()
    t0 = time.perf_counter()
    bad, losses = 0, []
    while True:
        tracer.tick(time.perf_counter() - t0)
        with tracer.span("bench.train_step"):
            metrics = _step(st, batch)
        losses.append(metrics["loss"])
        with tracer.span("bench.batch_prep"):
            batch = _batch(cell, st)
        with tracer.span("bench.wait_loss"):
            bad += bool(metrics["nonfinite"])  # blocks until the step is done
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(losses[-1])
    window_s = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    n = len(losses)
    tokens = n * tr["rows"] * cell.chips * tr["seq"]
    return {
        "run_start": run_start, "window_start": t0, "window_s": window_s,
        "lead_in": "reference's forward for the first measured batch",
        "attempted": n, "failed": bad, "counters0": counters0,
        "counts": {"steps": n, "tokens": tokens, "window_s": window_s},
        "losses": losses, "ref_loss": ref_loss,
        "log": [
            f"warm-up losses {st['warm_losses']}",
            f"window {window_s:.3f}s: {n} steps of {tr['rows'] * cell.chips}x"
            f"{tr['seq']} tokens, {bad} non-finite; loss first "
            f"{losses[0]:.4f} last {losses[-1]:.4f}",
        ],
    }


def check(cell, st, result):
    tol = cell.config["tol"]["loss"]
    losses, ref = result["losses"], result["ref_loss"]
    diff = abs(losses[0] - ref)
    ok = bool(np.isfinite(losses).all()) and diff <= tol and not result["failed"]
    return ok, (
        f"first measured step's loss {losses[0]:.5f}, reference (f32, "
        f"highest) {ref:.5f}, difference {diff:.5f} (tolerance {tol}); "
        f"all {len(losses)} losses finite: {bool(np.isfinite(losses).all())}"
    )


def traced_counts(cell, result, tracer, telemetry):
    return {}
