"""Training steps of an Olmo-Hybrid cell (family ``olmo_hybrid``): the
state-space cells' loop (``train_steps_ssm._window``, one step kept
queued behind the one that runs) and comparison functions
(``train_steps_ssm.compare`` over ``train_steps_routed.compare``,
``train_steps_routed.gradient_ok``), imported and not edited, with a
``correct`` of two parts:

* the loss: the first measured step's against the float32 ``highest``
  reference's on the same batch and parameters (``tol.loss``);
* **the step's gradient**, read back from Adam's first moment, against
  ``jax.grad`` of the reference (the delta rule one position at a time)
  over the embedding, layer 0 (Gated DeltaNet) and layer 3 (full
  attention): ``tol.gradient`` on the leaves and on the embedding's rows
  gathered by their token's position in a time chunk of the delta rule
  (``embed rows at t % chunk == p``: a state not carried across a chunk
  boundary moves the rows beside one), ``tol.gradient_rows`` on the
  median row of the embedding's gradient.  The reference's head and loss
  go in row blocks (``train_steps_ssm.blocked_loss``).  Adam's two moments
  wait on the host while it runs: the reference's gradient asks 10.6 GiB
  beside the parameters (compile-only, v5e), the moments are 4.5 GiB of
  the chip's 15.75.  The first moment's copy from before the step stays
  on the host too: the step's 8 GB of temporaries are reserved in one
  piece beside the resident state.

A run on a TPU is also incorrect if either mixer ran ``impl=jnp`` or
through the interpreter.
"""

import functools
import gc
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from reference import common

from . import train_steps
from . import train_steps_routed as routed
from . import train_steps_ssm as ssm

build = ssm.build


def compared(tree):
    """The leaves whose gradient is compared: the embedding, layer 0 (the
    first linear layer) and layer 3 (the first full layer)."""
    def first(stack):
        return jax.tree.map(lambda a: a[0], stack)

    return {
        "embed": tree["embed"],
        "linear[0]": first(tree["linear_layers"]),
        "full[0]": first(tree["full_layers"]),
    }


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype"))
def reference_gradient(params, tokens, targets, *, ref, sizes, dtype):
    """``jax.grad`` of the reference's loss with respect to the compared
    leaves, in float32."""
    rest = {
        name: jax.tree.map(lambda a: a[1:], params[name])
        for name in ("linear_layers", "full_layers")
    }

    def loss(leaves):
        def first_then_rest(key, name):
            return [jax.tree.map(lambda a: a[None], leaves[key]), rest[name]]

        p = dict(
            params, embed=leaves["embed"],
            linear_layers=first_then_rest("linear[0]", "linear_layers"),
            full_layers=first_then_rest("full[0]", "full_layers"),
        )
        with common.precision(dtype):
            x = ref.hidden(p, tokens, dict(sizes), dtype)
            return ssm.blocked_loss(ref, p, x, targets, dtype)

    return jax.grad(loss)(
        jax.tree.map(lambda a: a.astype(jnp.float32), compared(params))
    )


def _step_gradient_gaps(cell, st) -> dict:
    """One more step of the timed step object, before the window, and the
    gaps of the gradient it took from the reference's."""
    import optax

    tr = cell.config["training"]
    b1 = inspect.signature(getattr(optax, tr["optimizer"])).parameters["b1"].default
    host, batch = train_steps._batch(cell, st)
    # The reference first: the step donates the parameters.  Adam's moments
    # wait on the host meanwhile, and the reference's gradient too, after:
    # the step needs the room.
    state = st["state"]
    shardings = jax.tree.map(lambda a: a.sharding, state.opt_state)
    moments = jax.device_get(state.opt_state)
    for a in jax.tree.leaves(state.opt_state):
        a.delete()
    g_ref = jax.device_get(
        reference_gradient(
            state.params, host["tokens"], host["targets"], ref=cell.ref,
            sizes=cell.check._freeze(cell.config), dtype=jnp.dtype(jnp.float32),
        )
    )
    st["state"] = state._replace(opt_state=jax.device_put(moments, shardings))
    mu = compared(routed._first_moment(moments))
    del moments
    train_steps._step(st, (host, batch))
    mu1 = compared(routed._first_moment(st["state"].opt_state))
    g = jax.tree.map(
        lambda m0, m1: (m1.astype(jnp.float32) - b1 * jnp.asarray(m0, jnp.float32))
        / (1 - b1),
        mu, mu1,
    )
    return ssm.compare(g, jax.device_put(g_ref), host["tokens"], cell.cfg.delta_chunk)


def kernel_faults(counters: dict, platform: str) -> list:
    """What makes a run on a TPU incorrect whatever it computed: either
    mixer built off its kernels, or through the interpreter."""
    if platform != "tpu":
        return []
    faults = []
    for op, impl in (("delta_rule", "delta_rule"), ("attention", "attention.dispatch")):
        if counters.get(f"{impl}{{impl=jnp}}", 0):
            faults.append(f"{op} was built with impl=jnp on a TPU")
    for op, name in (("delta_rule", "delta_rule"), ("flash", "attention.flash")):
        if counters.get(f"{name}{{interpret=true}}", 0):
            faults.append(f"a {op} kernel ran in interpret mode on a TPU")
    return faults


def check(cell, st, result):
    from torchdistx_tpu import telemetry

    ok, detail = train_steps.check(cell, st, result)
    grad_ok, grad_detail = routed.gradient_ok(cell, st["gradient"])
    faults = kernel_faults(telemetry.counters(), jax.devices()[0].platform)
    return ok and grad_ok and not faults, "; ".join(
        [detail, grad_detail] + [f"FAULT: {f}" for f in faults]
    )


def run(cell, st, seconds, tracer):
    t = time.perf_counter()
    st["gradient"] = _step_gradient_gaps(cell, st)
    gradient_s = time.perf_counter() - t
    st["called"].clear()
    # A full collection inside the window is one long step: collect now,
    # keep what is alive out of the window's collections (train_steps_routed).
    gc.collect()
    gc.freeze()
    try:
        result = ssm._window(cell, st, seconds, tracer)
    finally:
        gc.unfreeze()
    period = 1e3 * np.diff(st["called"])
    result["traced"] = routed._all_device_ops(tracer)
    if tracer.on:
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            (st["state"], train_steps._batch(cell, st)[1]),
        )
        result["lowered"] = st["jitted"].lower(*abstract)
    gaps = st["gradient"]["gaps"]
    groups = {k: v for k, v in gaps.items() if k.startswith("embed rows")}
    result["log"] += [
        f"gradient comparison before the window: {gradient_s:.2f} s (set-up, "
        "outside every part); gaps by leaf "
        + ", ".join(f"{k} {v:.3g}" for k, v in gaps.items() if k not in groups)
        + f"; embedding rows by position in a chunk, {len(groups)} groups: "
        f"{min(groups.values()):.3g}-{max(groups.values()):.3g}",
        f"step period on the host's clock, ms: median {np.median(period):.1f}"
        f", 10th and 90th percentile {np.percentile(period, 10):.1f} and "
        f"{np.percentile(period, 90):.1f}",
    ]
    return result


# ``memory_analysis()`` of the step into the log, and the full list of the
# traced stretch's device operations into ``counts``.
traced_counts = routed.traced_counts
