"""``train_steps_routed`` with ONE STEP KEPT QUEUED behind the one that
runs: the routed driver's build, comparison and counts (imported, none of
them edited) around ``train_steps_ssm``'s window.

The routed driver's loop waits for each step before it hands over the next,
so the chip idles for the host's turn-around, and on the shared host that
turn-around has two levels between processes (PERF.md section 7.9: a step
lost in one run of a dozen, spread 0.019 against a bound of 0.01).
``train_steps_ssm._window`` waits for the step BEFORE the one it has just
handed over, as a training job does that reads its loss a step late, so the
period is the device's.

``correct`` is ``train_steps_routed.check``'s: the first measured step's
loss, and the step's gradient (read back from Adam's first moment) against
``jax.grad`` of the float32 ``highest`` reference on the same batch and
parameters, over the embedding, the dense stack and the first expert layer
(``tol.gradient`` on leaves and experts' slices, ``tol.gradient_rows`` on
the median row of the embedding's gradient).  The reference's gradient is
taken here with its head and loss in row blocks
(``train_steps_ssm.blocked_loss``): the (8192, 50048) float32 logits and
their log-softmax never exist whole beside the resident state.
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

build = routed.build
check = routed.check
traced_counts = routed.traced_counts


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype"))
def reference_gradient(params, tokens, targets, *, ref, sizes, dtype):
    """``routed.reference_gradient`` with the loss in row blocks."""
    rest = jax.tree.map(lambda a: a[1:], params["moe_layers"])

    def loss(leaves):
        first = jax.tree.map(lambda a: a[None], leaves["moe_layers[0]"])
        p = dict(
            params, embed=leaves["embed"],
            dense_layers=leaves["dense_layers"], moe_layers=[first, rest],
        )
        with common.precision(dtype):
            x = ref.hidden(p, tokens, dict(sizes), dtype)
            return ssm.blocked_loss(ref, p, x, targets, dtype)

    return jax.grad(loss)(routed.compared(params))


def _step_gradient_gaps(cell, st) -> dict:
    """One more step of the timed step object, before the window, and the
    gaps of the gradient it took from the reference's."""
    import optax

    tr = cell.config["training"]
    b1 = inspect.signature(getattr(optax, tr["optimizer"])).parameters["b1"].default
    host, batch = train_steps._batch(cell, st)
    # The reference first: the step donates the parameters.  Its gradient
    # waits on the host, the step needs the room.
    g_ref = jax.device_get(
        reference_gradient(
            st["state"].params, host["tokens"], host["targets"], ref=cell.ref,
            sizes=cell.check._freeze(cell.config), dtype=jnp.dtype(jnp.float32),
        )
    )
    first_moment = routed._first_moment
    mu = jax.tree.map(
        jnp.copy, routed.compared(first_moment(st["state"].opt_state))
    )
    train_steps._step(st, (host, batch))
    mu1 = routed.compared(first_moment(st["state"].opt_state))
    g = jax.tree.map(
        lambda m0, m1: (m1.astype(jnp.float32) - b1 * m0.astype(jnp.float32))
        / (1 - b1),
        mu, mu1,
    )
    return routed.compare(g, jax.device_put(g_ref), host["tokens"])


def run(cell, st, seconds, tracer):
    t = time.perf_counter()
    st["gradient"] = _step_gradient_gaps(cell, st)
    gradient_s = time.perf_counter() - t
    st["moe"].clear()  # the warm-up's and the compared step's
    st["called"].clear()
    # A full collection inside the window is one long step: collect now,
    # keep what is alive out of the window's collections (train_steps_routed).
    gc.collect()
    gc.freeze()
    try:
        result = ssm._window(cell, st, seconds, tracer)
    finally:
        gc.unfreeze()
    # A step is handed over when the one before the last has finished.
    period = 1e3 * np.diff(st["called"])
    moe = [{k: float(v) for k, v in m.items()} for m in st["moe"]]
    result["counts"].update(
        local_assignments_per_step=float(
            np.mean([m["local_assignments"] for m in moe])
        ),
        load_max_over_mean=float(
            np.mean([m["load_max_over_mean"] for m in moe])
        ),
    )
    result["traced"] = routed._all_device_ops(tracer)
    if tracer.on:
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            (st["state"], train_steps._batch(cell, st)[1]),
        )
        result["lowered"] = st["jitted"].lower(*abstract)
    gaps = st["gradient"]["gaps"]
    slow = np.flatnonzero(period > 1.05 * np.median(period))
    result["log"] += [
        f"gradient comparison before the window: {gradient_s:.2f} s (set-up, "
        "outside every part); gaps by leaf "
        + ", ".join(f"{k} {v:.3g}" for k, v in gaps.items() if k.endswith("']"))
        + "; experts' slices "
        + ", ".join(
            f"{leaf} {min(v):.3g}-{max(v):.3g}"
            for leaf, v in routed._slices(gaps).items()
        ),
        f"step period on the host's clock, ms: median {np.median(period):.1f}"
        f", 10th and 90th percentile {np.percentile(period, 10):.1f} and "
        f"{np.percentile(period, 90):.1f}; {len(slow)} of {len(period)} over "
        "1.05 x the median"
        + "".join(f", step {i} {period[i]:.1f}" for i in slow[:5]),
        f"routed to held experts: {result['counts']['local_assignments_per_step']:.0f} "
        f"assignments a step, busiest over mean "
        f"{result['counts']['load_max_over_mean']:.3f}",
    ]
    return result
