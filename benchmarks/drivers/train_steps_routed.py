"""``train_steps`` for a family whose step carries routing counts out
(``metrics["moe"]``: device scalars, no host sync inside the step): the
same build and loop, with the step wrapped so that each step's counts are
kept, and after the window two more entries in ``counts``:

* ``local_assignments_per_step``: (token, choice) pairs the step really
  routed to experts held here, summed over the expert layers, mean over the
  window's steps;
* ``load_max_over_mean``: the busiest held expert's assignments over the
  held experts' mean, mean over expert layers and the window's steps.

``correct`` takes a second comparison besides ``train_steps.check``'s
loss, because at step 0 with random weights every logit is near 0 and the
loss reads the same whatever the layers compute: **the step's gradient
against the plain reference's**.  Just before the window the timed step
object runs once more on a fresh batch, and the gradient its optimizer
received is read back from Adam's first moment, ``g = (mu' - b1 mu) /
(1 - b1)`` (the step hands out no gradient, and the chip has no room for a
second program's); ``jax.grad`` of the float32 ``highest`` reference on
the same batch and parameters gives the other side.  Compared are the
embedding, the dense stack and the first expert layer, the layers deepest
below the loss: their gradients have crossed every layer's backward pass,
every flash kernel and every routed-expert layer.  A gap is ``|g - g_ref|
/ |g_ref|`` (1 = a state left unchanged), and two limits hold it:

* ``tol.gradient``, structure: the worst gap over the compared leaves and,
  in a leaf with a leading expert axis (``e_*``), over each held expert's
  slice.  bf16 hidden states feed a float32 router, so a few near-tied
  sixth choices differ from the reference's and whole tokens change
  experts: these gaps read tenths of a percent of the ASSIGNMENTS, not
  rounding, and the limit lies between them and 1 (an expert the routed
  sum skips reads 1).
* ``tol.gradient_rows``, precision: the MEDIAN gap over the embedding
  gradient's rows (one row a token of the batch: what came back down
  through every layer to that position).  The tokens whose routing
  differs are a minority, so the median reads rounding.

In a traced run it also keeps ``device_ops_all``: ``trace.reduce``'s
``device_ops`` with no cut at ten rows (``run.py`` hands on the ten largest
only, and the expert layer's grouped products alone are eleven
instructions), for the readers of the kernels' shares, and logs XLA's
``memory_analysis()`` of the step.
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


def build(cell, params):
    st = train_steps.build(cell, params)
    step_fn, st["moe"], st["called"] = st["step_fn"], [], []

    def keeping(state, batch):
        st["called"].append(time.perf_counter())
        state, metrics = step_fn(state, batch)
        st["moe"].append(metrics["moe"])
        return state, metrics

    st["step_fn"], st["jitted"] = keeping, step_fn
    return st


def compared(tree):
    """Of a tree shaped like the parameters, the leaves whose gradient is
    compared: the embedding, the dense stack and the first expert layer."""
    return {
        "embed": tree["embed"],
        "dense_layers": tree["dense_layers"],
        "moe_layers[0]": jax.tree.map(lambda a: a[0], tree["moe_layers"]),
    }


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype"))
def reference_gradient(params, tokens, targets, *, ref, sizes, dtype):
    """``jax.grad`` of the plain reference's loss with respect to the
    compared leaves, in the parameters' own dtype."""

    rest = jax.tree.map(lambda a: a[1:], params["moe_layers"])

    def loss(leaves):
        first = jax.tree.map(lambda a: a[None], leaves["moe_layers[0]"])
        p = dict(
            params, embed=leaves["embed"],
            dense_layers=leaves["dense_layers"], moe_layers=[first, rest],
        )
        with common.precision(dtype):
            x = ref.hidden(p, tokens, dict(sizes), dtype)
            return common.cross_entropy(ref.head(p, x, dtype), targets)

    return jax.grad(loss)(compared(params))


@functools.partial(jax.jit, static_argnames="axes")
def _norms(g, g_ref, axes=None):
    g, g_ref = g.astype(jnp.float32), g_ref.astype(jnp.float32)
    return (
        jnp.sqrt(jnp.sum(jnp.square(g - g_ref), axis=axes)),
        jnp.sqrt(jnp.sum(jnp.square(g_ref), axis=axes)),
    )


def _gap(diff, norm) -> float:
    """A part the reference leaves alone (the selection bias) must be left
    alone: its gap is 0 then, and infinite otherwise."""
    diff, norm = float(diff), float(norm)
    return diff / norm if norm else (0.0 if diff == 0 else float("inf"))


def gaps(g, g_ref) -> dict:
    """``|g - g_ref| / |g_ref|`` of every compared leaf by its path, and
    of each expert's slice of a leaf with a leading expert axis."""
    out = {}
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g), jax.tree.leaves(g_ref)
    ):
        name = jax.tree_util.keystr(path)
        out[name] = _gap(*_norms(a, b))
        if path[-1].key.startswith("e_"):
            diffs, norms = _norms(a, b, axes=tuple(range(1, a.ndim)))
            for e, (diff, norm) in enumerate(zip(diffs, norms)):
                out[f"{name}[{e}]"] = _gap(diff, norm)
    return out


def row_gaps(g, g_ref, rows) -> np.ndarray:
    """The gap of each of ``rows`` of a matrix."""
    diffs, norms = (np.asarray(x)[rows] for x in _norms(g, g_ref, axes=1))
    return diffs / norms


def _first_moment(opt_state):
    return next(s.mu for s in opt_state if hasattr(s, "mu"))


def _step_gradient_gaps(cell, st) -> dict:
    """One more step of the timed step object, before the window, and the
    gaps of the gradient it took from the reference's (module docstring)."""
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
    mu = jax.tree.map(jnp.copy, compared(_first_moment(st["state"].opt_state)))
    train_steps._step(st, (host, batch))
    mu1 = compared(_first_moment(st["state"].opt_state))
    g = jax.tree.map(
        lambda m0, m1: (m1.astype(jnp.float32) - b1 * m0.astype(jnp.float32))
        / (1 - b1),
        mu, mu1,
    )
    return compare(g, jax.device_put(g_ref), host["tokens"])


def compare(g, g_ref, tokens) -> dict:
    """Both readings of a gradient against the reference's: ``gaps`` of
    the leaves, and ``rows``, the embedding's row gaps for the batch's
    tokens."""
    return {
        "gaps": gaps(g, g_ref),
        "rows": row_gaps(
            g["embed"]["weight"], g_ref["embed"]["weight"], np.unique(tokens)
        ),
    }


def gradient_ok(cell, reading) -> tuple:
    """``(ok, detail)`` of a gradient's reading (``compare``) against
    ``tol.gradient`` and ``tol.gradient_rows``."""
    tol = cell.config["tol"]
    leaf_gaps, rows = reading["gaps"], reading["rows"]
    worst = max(leaf_gaps, key=lambda k: np.nan_to_num(leaf_gaps[k], nan=np.inf))
    median = float(np.median(rows))
    ok = (
        all(gap <= tol["gradient"] for gap in leaf_gaps.values())
        and median <= tol["gradient_rows"]
    )
    return ok, (
        f"step's gradient against the reference's (f32, highest), "
        f"|g - g_ref| / |g_ref|: worst of {len(leaf_gaps)} leaves and "
        f"experts' slices {leaf_gaps[worst]:.5f} at {worst} (tolerance "
        f"{tol['gradient']}); median of the embedding's {len(rows)} rows "
        f"{median:.5f} (tolerance {tol['gradient_rows']}), 90th percentile "
        f"{np.percentile(rows, 90):.5f}"
    )


def check(cell, st, result):
    ok, detail = train_steps.check(cell, st, result)
    grad_ok, grad_detail = gradient_ok(cell, st["gradient"])
    return ok and grad_ok, f"{detail}; {grad_detail}"


def _slices(leaf_gaps) -> dict:
    """The experts' slice gaps, gathered by leaf."""
    out = {}
    for k, v in leaf_gaps.items():
        if not k.endswith("']"):
            out.setdefault(k[: k.rindex("[")], []).append(v)
    return out


def _all_device_ops(tracer) -> dict:
    """The traced stretch's device operations, every one of them.  Read
    here, after the window, because ``run.py`` deletes the trace once it
    has its own reduction."""
    if tracer.dir is None:
        return {}
    from benchlib import trace

    tracer.stop()
    full = trace.reduce(trace.find_xplane(tracer.dir), top=1 << 30)
    return {"device_ops_all": full["device_ops"]} if full else {}


def run(cell, st, seconds, tracer):
    t = time.perf_counter()
    st["gradient"] = _step_gradient_gaps(cell, st)
    gradient_s = time.perf_counter() - t
    st["moe"].clear()  # the warm-up's and the compared step's
    st["called"].clear()
    # A full collection walks every object of the process (torch,
    # transformers, the traced programs): one that falls inside the window
    # is one long step.  Collect now and keep what is alive out of the
    # window's collections; those are timed.
    t = time.perf_counter()
    gc.collect()
    full_s, pauses, began = time.perf_counter() - t, [], []

    def timed(phase, info):
        if phase == "start":
            began.append(time.perf_counter())
        else:
            pauses.append((began[-1], info["generation"], time.perf_counter() - began.pop()))

    gc.freeze()
    gc.callbacks.append(timed)
    try:
        result = train_steps.run(cell, st, seconds, tracer)
    finally:
        gc.callbacks.remove(timed)
        gc.unfreeze()
    pauses = [
        (at - result["window_start"], gen, s) for at, gen, s in pauses
        if at >= result["window_start"] and s >= 0.01
    ]
    period = 1e3 * np.diff(st["called"])  # the loop waits for each step
    moe = [{k: float(v) for k, v in m.items()} for m in st["moe"]]
    result["counts"].update(
        local_assignments_per_step=float(
            np.mean([m["local_assignments"] for m in moe])
        ),
        load_max_over_mean=float(
            np.mean([m["load_max_over_mean"] for m in moe])
        ),
    )
    result["traced"] = _all_device_ops(tracer)
    if tracer.on:
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            (st["state"], train_steps._batch(cell, st)[1]),
        )
        result["lowered"] = st["jitted"].lower(*abstract)
    slow = np.flatnonzero(period > 1.05 * np.median(period))
    longest = slow[np.argsort(-period[slow])][:5]
    result["log"] += [
        f"gradient comparison before the window: {gradient_s:.2f} s (set-up, "
        "outside every part); gaps by leaf "
        + ", ".join(
            f"{k} {v:.3g}" for k, v in st["gradient"]["gaps"].items()
            if k.endswith("']")  # the experts' slices: their range only
        ) + "; experts' slices "
        + ", ".join(
            f"{leaf} {min(v):.3g}-{max(v):.3g}" for leaf, v in _slices(
                st["gradient"]["gaps"]
            ).items()
        ),
        f"step period on the host's clock, ms: median {np.median(period):.1f}"
        f", 10th and 90th percentile {np.percentile(period, 10):.1f} and "
        f"{np.percentile(period, 90):.1f}; {len(slow)} of {len(period)} over "
        "1.05 x the median, "
        f"{(period[slow] - np.median(period)).sum():.0f} ms lost to them"
        + "".join(f", step {i} {period[i]:.1f}" for i in longest),
        f"collector: the full collection before the window took {full_s:.2f} s; "
        f"inside the window {len(pauses)} collections of 10 ms or more"
        + "".join(f", generation {g} {1e3 * s:.0f} ms at {at:.1f} s" for at, g, s in pauses),
        f"routed to held experts: {result['counts']['local_assignments_per_step']:.0f} "
        f"assignments a step, busiest over mean "
        f"{result['counts']['load_max_over_mean']:.3f}",
    ]
    return result


def traced_counts(cell, result, tracer, telemetry):
    # After run.py has taken the window's compile counters: compiling the
    # step again finds it in the compile cache, but counts as a compile.
    m = result["lowered"].compile().memory_analysis()
    gib = 2.0**30
    print(
        f"memory_analysis() of the step: temp {m.temp_size_in_bytes / gib:.3f}"
        f" + arguments {m.argument_size_in_bytes / gib:.3f} GiB (outputs "
        f"{m.output_size_in_bytes / gib:.3f}, aliased "
        f"{m.alias_size_in_bytes / gib:.3f})", flush=True,
    )
    return result["traced"]
