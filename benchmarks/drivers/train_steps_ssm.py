"""``train_steps`` for a state-space family (``models/jamba.py``): the same
build and loop, and a ``correct`` that holds the STEP'S GRADIENT against
the plain reference's, because at step 0 with random weights every logit
is near 0 and the loss reads ln(vocabulary) whatever the layers compute
(PERF.md section 6, PR 27).

Just before the window the timed step object runs once more on a fresh
batch, and the gradient its optimizer received is read back from Adam's
first moment, ``g = (mu' - b1 mu) / (1 - b1)``, as
``drivers/train_steps_routed`` reads it (its comparison functions are used
here); ``jax.grad`` of the float32 ``highest`` reference on the same batch
and parameters gives the other side, with respect to the compared leaves
only and its head and loss in row blocks (the state is resident meanwhile,
8.9 GiB of the chip's 15.75).  Compared: the embedding (what came down
through every layer and every scan, and the tied head's own part) and
LAYER 0's leaves (``W_in``, the convolution, ``W_x``, ``W_dt``, the three
small norms, ``A_log``, ``D``, ``W_out``, the feed-forward).  A gap is
``|g - g_ref| / |g_ref|`` (1 = a state left unchanged).  Two limits:

* ``tol.gradient_rows``, precision: the MEDIAN gap over the embedding
  gradient's rows for the batch's tokens.  A scan whose state is kept in
  bfloat16, or float8 operands in the mixer's products, move every row.
* ``tol.gradient``, structure: the worst gap over the compared leaves AND
  over the embedding's rows gathered by their token's position within a
  time chunk of the scan (``embed rows at t % chunk == p``: the MEDIAN row
  gap of the about ``T / chunk`` tokens there).  A convolution that misses a
  tap or a skipped ``D * u`` moves whole leaves; a state reset at a chunk
  boundary moves little of any leaf (with this initialisation a state
  forgets within a few positions) but all of the rows whose tokens sit
  beside a boundary.

A run is also incorrect if, on a TPU, a scan was built with ``impl=jnp``
or an interpreted scan kernel.  In a traced run it keeps
``device_ops_all`` (every device operation of the traced stretch, for the
readers of the kernels' shares) and logs XLA's ``memory_analysis()`` of
the step.

The window is ``train_steps.run``'s with ONE STEP KEPT QUEUED behind the
one that runs (``_window``): the loop waits for the step BEFORE the one it
has just handed over, as a training job does that reads its loss a step
late.  ``train_steps.run`` waits for each step before it hands over the
next, so the chip idles for the host's turn-around, and on the shared host
that turn-around has two levels between processes, 2.3 and 4.9 ms of a
433 ms step, with the device's time an execution the same 431.1 ms in both
(PERF.md section 6, PR 31): six runs then spread by more than half
``train_tok_s``' bound for no reason in the program.
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

HEAD_ROWS = 1024


def build(cell, params):
    st = train_steps.build(cell, params)
    step_fn, st["called"] = st["step_fn"], []

    def timed(state, batch):
        st["called"].append(time.perf_counter())
        return step_fn(state, batch)

    st["step_fn"], st["jitted"] = timed, step_fn
    return st


def _layer0(periods):
    """Layer 0's leaves: the first layer of the first period's leading
    Mamba stack (of its attention layer where the offset is 0)."""
    if "mamba_a" in periods:
        return jax.tree.map(lambda a: a[0, 0], periods["mamba_a"])
    return jax.tree.map(lambda a: a[0], periods["attn"])


def compared(tree):
    """Of a tree shaped like the parameters, the leaves whose gradient is
    compared: the embedding and layer 0."""
    return {"embed": tree["embed"], "layer0": _layer0(tree["periods"])}


def blocked_loss(ref, params, x, targets, dtype):
    """Mean cross-entropy through the reference's head, ``HEAD_ROWS`` rows
    of the flattened batch at a time, each block computed again in a
    gradient: the (8192, 65536) float32 logits never exist whole."""
    rows = x.reshape(-1, x.shape[-1])
    flat = targets.reshape(-1)
    size = min(HEAD_ROWS, rows.shape[0])
    if rows.shape[0] % size:
        size = rows.shape[0]

    @jax.checkpoint
    def block(total, xs):
        xb, tb = xs
        logp = jax.nn.log_softmax(ref.head(params, xb, dtype), axis=-1)
        return total - jnp.take_along_axis(logp, tb[:, None], axis=-1).sum(), None

    total, _ = jax.lax.scan(
        block, jnp.zeros((), jnp.float32),
        (rows.reshape(-1, size, rows.shape[-1]), flat.reshape(-1, size)),
    )
    return total / flat.shape[0]


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype"))
def reference_gradient(params, tokens, targets, *, ref, sizes, dtype):
    """``jax.grad`` of the plain reference's loss with respect to the
    compared leaves, in float32."""

    def loss(leaves):
        p = dict(params, **leaves)  # layer 0 apart from its stack
        with common.precision(dtype):
            x = ref.hidden(p, tokens, dict(sizes), dtype)
            return blocked_loss(ref, p, x, targets, dtype)

    return jax.grad(loss)(
        jax.tree.map(lambda a: a.astype(jnp.float32), compared(params))
    )


def compare(g, g_ref, tokens, chunk) -> dict:
    """``routed.compare``'s two readings, and among the gaps the MEDIAN
    row gap of the embedding's rows gathered by their token's position in
    a time chunk (a median, because a group is some thirty rows and the
    norm of their sum is the noisiest row's)."""
    reading = routed.compare(g, g_ref, tokens)
    # A row a token: rows of tokens drawn twice are left out of the groups.
    ids, first, count = np.unique(tokens, return_index=True, return_counts=True)
    ids, pos = ids[count == 1], first[count == 1] % tokens.shape[-1] % chunk
    gaps = routed.row_gaps(g["embed"]["weight"], g_ref["embed"]["weight"], ids)
    for p in range(min(chunk, tokens.shape[-1])):
        if (pos == p).any():
            reading["gaps"][f"embed rows at t % {chunk} == {p}"] = float(
                np.median(gaps[pos == p])
            )
    return reading


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
    mu = jax.tree.map(
        jnp.copy, compared(routed._first_moment(st["state"].opt_state))
    )
    train_steps._step(st, (host, batch))
    mu1 = compared(routed._first_moment(st["state"].opt_state))
    g = jax.tree.map(
        lambda m0, m1: (m1.astype(jnp.float32) - b1 * m0.astype(jnp.float32))
        / (1 - b1),
        mu, mu1,
    )
    return compare(g, jax.device_put(g_ref), host["tokens"], cell.cfg.scan_chunk)


def scan_faults(counters: dict, platform: str) -> list:
    """What makes a run on a TPU incorrect whatever it computed: a scan
    built off the kernels, or through the interpreter."""
    if platform != "tpu":
        return []
    faults = []
    if counters.get("ssm.scan{impl=jnp}", 0):
        faults.append("a selective scan was built with impl=jnp on a TPU")
    if counters.get("ssm.scan{interpret=true}", 0):
        faults.append("a scan kernel ran in interpret mode on a TPU")
    return faults


def check(cell, st, result):
    from torchdistx_tpu import telemetry

    ok, detail = train_steps.check(cell, st, result)
    grad_ok, grad_detail = routed.gradient_ok(cell, st["gradient"])
    faults = scan_faults(telemetry.counters(), jax.devices()[0].platform)
    return ok and grad_ok and not faults, "; ".join(
        [detail, grad_detail] + [f"FAULT: {f}" for f in faults]
    )


def _profiler_turns(tracer, elapsed: float) -> bool:
    """Whether ``tracer.tick(elapsed)`` will start or stop the profiler.
    The loop then lets the queued step finish first, so that the trace
    begins and ends on an idle chip, as ``train_steps.run``'s does:
    ``benchlib/trace.reduce`` counts an execution that the trace's end cut
    short as a whole one, and the kernels' shares divide by that count."""
    if not tracer.on or tracer.t1 is not None:
        return False
    return elapsed >= tracer.start_s + (tracer.length_s if tracer.active else 0.0)


def _window(cell, st, seconds, tracer):
    """``train_steps.run``: the same batches, spans, reference loss and
    result, but the loop waits for the step BEFORE the one it has just
    handed over, so one step is always queued behind the one that runs and
    the host's turn-around is off the chip's path (module docstring).  The
    window closes when the last step handed over is done."""
    from torchdistx_tpu import telemetry

    run_start = time.perf_counter()
    batch = train_steps._batch(cell, st)
    ref_loss = cell.check.loss(
        cell.ref, st["state"].params, cell.config, batch[0]["tokens"],
        batch[0]["targets"],
    )
    tr = cell.config["training"]
    counters0 = telemetry.counters()
    t0 = time.perf_counter()
    bad, losses, before = 0, [], None
    while True:
        elapsed = time.perf_counter() - t0
        if before is not None and _profiler_turns(tracer, elapsed):
            jax.block_until_ready(before)
        tracer.tick(elapsed)
        with tracer.span("bench.train_step"):
            metrics = train_steps._step(st, batch)
        losses.append(metrics["loss"])
        with tracer.span("bench.batch_prep"):
            batch = train_steps._batch(cell, st)
        with tracer.span("bench.wait_loss"):
            if before is not None:
                bad += bool(before["nonfinite"])  # blocks until it is done
        before = metrics
        if time.perf_counter() - t0 >= seconds:
            break
    bad += bool(before["nonfinite"])
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
            f"{tr['seq']} tokens, one queued behind the one that runs, {bad} "
            f"non-finite; loss first {losses[0]:.4f} last {losses[-1]:.4f}",
        ],
    }


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
        result = _window(cell, st, seconds, tracer)
    finally:
        gc.unfreeze()
    # A step is handed over when the one before the last has finished.
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
    slow = np.flatnonzero(period > 1.05 * np.median(period))
    result["log"] += [
        f"gradient comparison before the window: {gradient_s:.2f} s (set-up, "
        "outside every part); gaps by leaf "
        + ", ".join(f"{k} {v:.3g}" for k, v in gaps.items() if k not in groups)
        + f"; embedding rows by position in a chunk, {len(groups)} groups: "
        f"{min(groups.values()):.3g}-{max(groups.values()):.3g}",
        f"step period on the host's clock, ms: median {np.median(period):.1f}"
        f", 10th and 90th percentile {np.percentile(period, 10):.1f} and "
        f"{np.percentile(period, 90):.1f}; {len(slow)} of {len(period)} over "
        "1.05 x the median"
        + "".join(f", step {i} {period[i]:.1f}" for i in slow[:5]),
    ]
    return result


# ``memory_analysis()`` of the step into the log, and the full list of the
# traced stretch's device operations into ``counts``.
traced_counts = routed.traced_counts
