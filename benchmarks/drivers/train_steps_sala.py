"""Training steps of a MiniCPM-SALA cell (family ``minicpm_sala``): the
routed queued driver's loop (``train_steps_ssm._window``, one step kept
queued behind the one that runs) and comparison functions
(``train_steps_routed``: ``compare``, ``gradient_ok``), imported and not
edited, with a ``correct`` of three parts:

* the loss: the first measured step's against the float32 ``highest``
  reference's on the same batch and parameters (``tol.loss``);
* **the choice of blocks** of the first sparse layer (layer 0): the
  program's, as its own code makes it in the model's dtype
  (``models.minicpm_sala.sparse_choice``), against the reference's float32
  choice on the same batch.  Every block where the two differ must be a
  near tie: its reference score within ``tol.select`` of the (token,
  group)'s 64th score, relatively (a forced block, or a token whose causal
  blocks are all chosen, has no near tie: infinite);
* **the step's gradient**, read back from Adam's first moment, against
  ``jax.grad`` of the reference under the PROGRAM's choice of blocks (a
  choice has no gradient, and a near tie chosen otherwise would move the
  rows of its tokens), over the embedding, layer 0 (sparse) and layer 1
  (lightning): ``tol.gradient`` on the leaves, ``tol.gradient_rows`` on
  the median row of the embedding's gradient.  The reference's head and
  loss go in row blocks (``train_steps_ssm.blocked_loss``).

A run on a TPU is also incorrect if either mixer ran ``impl=jnp`` or
through the interpreter.  After the window ``counts["sparse_kv_blocks"]``
is the mean over its steps of ``aux["sparse"]["kv_blocks"]`` (the keys a q
tile's kernel steps run, in blocks of 64, over the sparse layers), each
step's also observed into the histogram ``sparse_attn.kv_blocks``.
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

_KV_BOUNDS = tuple(float(2**i) for i in range(10))


def build(cell, params):
    st = train_steps.build(cell, params)
    step_fn, st["sparse"], st["called"] = st["step_fn"], [], []

    def keeping(state, batch):
        st["called"].append(time.perf_counter())
        state, metrics = step_fn(state, batch)
        if "sparse" in metrics:
            st["sparse"].append(metrics["sparse"]["kv_blocks"])
        return state, metrics

    st["step_fn"], st["jitted"] = keeping, step_fn
    return st


def compared(tree):
    """The leaves whose gradient is compared: the embedding, layer 0 (the
    first sparse layer) and layer 1 (the first lightning layer)."""
    def first(stack):
        return jax.tree.map(lambda a: a[0], stack)

    return {
        "embed": tree["embed"],
        "sparse[0]": first(tree["sparse_layers"]),
        "lightning[0]": first(tree["lightning_layers"]),
    }


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype"))
def reference_gradient(params, tokens, targets, chosen, *, ref, sizes, dtype):
    """``jax.grad`` of the reference's loss with respect to the compared
    leaves, in float32, the first sparse layer attending ``chosen``."""
    rest = {
        name: jax.tree.map(lambda a: a[1:], params[name])
        for name in ("sparse_layers", "lightning_layers")
    }

    def loss(leaves):
        def first_then_rest(key, name):
            return [jax.tree.map(lambda a: a[None], leaves[key]), rest[name]]

        p = dict(
            params, embed=leaves["embed"],
            sparse_layers=first_then_rest("sparse[0]", "sparse_layers"),
            lightning_layers=first_then_rest("lightning[0]", "lightning_layers"),
        )
        with common.precision(dtype):
            x = ref.hidden(p, tokens, dict(sizes), dtype, choices=[chosen])
            return ssm.blocked_loss(ref, p, x, targets, dtype)

    return jax.grad(loss)(
        jax.tree.map(lambda a: a.astype(jnp.float32), compared(params))
    )


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype"))
def reference_choice(params, tokens, *, ref, sizes, dtype=jnp.dtype(jnp.float32)):
    """The reference's choice for the first sparse layer (float32 unless
    told otherwise) and the scores that decided it, ``(chosen, ranked)``
    ``(B, Hkv, T, NB)``, query rows in blocks of ``ref.ROWS``."""
    sizes = dict(sizes)
    with common.precision(dtype):
        q, k = ref.first_sparse_qk(params, tokens, sizes, dtype)
        t, size = q.shape[1], min(ref.ROWS, q.shape[1])
        chosen, ranked = jax.lax.map(
            lambda lo: ref.choice_scores(q, k, sizes, lo + jnp.arange(size)),
            jnp.arange(0, t, size),
        )
    return tuple(
        jnp.moveaxis(x, 0, 2).reshape(*x.shape[1:3], t, -1)
        for x in (chosen, ranked)
    )


def choice_gaps(program, chosen, ranked, topk) -> dict:
    """Where the program's choice differs from the reference's: how far
    each differing block's reference score lies from the (token, group)'s
    ``topk``-th, relatively.  ``select``: the largest such gap (0 where
    the choices agree)."""
    program, chosen, ranked = (np.asarray(x) for x in (program, chosen, ranked))
    differ = program != chosen
    rows = differ.any(-1)
    kth = -np.sort(-ranked, axis=-1)[..., topk - 1: topk]  # (B, Hkv, T, 1)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(ranked - kth) / np.abs(kth)
    gap = np.where(np.isfinite(kth), gap, np.inf)  # no near tie to be had
    gap = np.where(np.isnan(gap), np.inf, gap)
    worst = float(gap[differ].max()) if differ.any() else 0.0
    return {
        "select": worst,
        "rows_differ": int(rows.sum()),
        "rows": int(rows.size),
        "blocks_differ": int(differ.sum()),
    }


def _sizes(cell):
    return cell.check._freeze(cell.config)


def _step_readings(cell, st) -> dict:
    """One more step of the timed step object, before the window: the
    choice check and the gaps of the gradient it took."""
    import optax

    from torchdistx_tpu.ops.sparse_attention import unpack_blocks

    tr = cell.config["training"]
    b1 = inspect.signature(getattr(optax, tr["optimizer"])).parameters["b1"].default
    host, batch = train_steps._batch(cell, st)
    params, nb = st["state"].params, tr["seq"] // cell.config["sparse_block_size"]
    words = jax.jit(
        lambda p, t: cell.model.sparse_choice(p, t, cell.cfg)
    )(params, batch["tokens"])
    program = unpack_blocks(words, nb)
    chosen, ranked = reference_choice(
        params, host["tokens"], ref=cell.ref, sizes=_sizes(cell)
    )
    choice = choice_gaps(program, chosen, ranked, cell.config["sparse_topk"])
    del chosen, ranked
    # The reference first: the step donates the parameters.  Its gradient
    # waits on the host, the step needs the room.
    g_ref = jax.device_get(
        reference_gradient(
            params, host["tokens"], host["targets"], program, ref=cell.ref,
            sizes=_sizes(cell), dtype=jnp.dtype(jnp.float32),
        )
    )
    del program, words
    first_moment = routed._first_moment
    mu = jax.tree.map(jnp.copy, compared(first_moment(st["state"].opt_state)))
    train_steps._step(st, (host, batch))
    mu1 = compared(first_moment(st["state"].opt_state))
    g = jax.tree.map(
        lambda m0, m1: (m1.astype(jnp.float32) - b1 * m0.astype(jnp.float32))
        / (1 - b1),
        mu, mu1,
    )
    reading = routed.compare(g, jax.device_put(g_ref), host["tokens"])
    reading["choice"] = choice
    return reading


def kernel_faults(counters: dict, platform: str) -> list:
    """What makes a run on a TPU incorrect whatever it computed: either
    mixer built off its kernels, or through the interpreter."""
    if platform != "tpu":
        return []
    faults = []
    for op in ("sparse_attn", "lightning"):
        if counters.get(f"{op}{{impl=jnp}}", 0):
            faults.append(f"{op} was built with impl=jnp on a TPU")
        if counters.get(f"{op}{{interpret=true}}", 0):
            faults.append(f"a {op} kernel ran in interpret mode on a TPU")
    return faults


def choice_ok(cell, choice) -> tuple:
    tol = cell.config["tol"]["select"]
    return choice["select"] <= tol, (
        f"layer 0's choice of blocks against the reference's (f32, highest): "
        f"{choice['rows_differ']} of {choice['rows']} (token, group) rows "
        f"differ in {choice['blocks_differ']} blocks, the farthest from its "
        f"row's 64th score by {choice['select']:.4g} of it (tolerance {tol})"
    )


def check(cell, st, result):
    from torchdistx_tpu import telemetry

    ok, detail = train_steps.check(cell, st, result)
    sel_ok, sel_detail = choice_ok(cell, st["gradient"]["choice"])
    grad_ok, grad_detail = routed.gradient_ok(cell, st["gradient"])
    faults = kernel_faults(telemetry.counters(), jax.devices()[0].platform)
    return ok and sel_ok and grad_ok and not faults, "; ".join(
        [detail, sel_detail, grad_detail] + [f"FAULT: {f}" for f in faults]
    )


def run(cell, st, seconds, tracer):
    from torchdistx_tpu import telemetry

    t = time.perf_counter()
    st["gradient"] = _step_readings(cell, st)
    gradient_s = time.perf_counter() - t
    st["sparse"].clear()  # the warm-up's and the compared step's
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
    kv = [float(x) for x in st["sparse"]]
    hist = telemetry.histogram("sparse_attn.kv_blocks", _KV_BOUNDS)
    for x in kv:
        hist.observe(x)
    if kv:
        result["counts"]["sparse_kv_blocks"] = float(np.mean(kv))
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
        f"choice and gradient comparison before the window: {gradient_s:.2f} s "
        "(set-up, outside every part); gaps by leaf "
        + ", ".join(f"{k} {v:.3g}" for k, v in gaps.items())
        + f"; choice {st['gradient']['choice']}",
        f"step period on the host's clock, ms: median {np.median(period):.1f}"
        f", 10th and 90th percentile {np.percentile(period, 10):.1f} and "
        f"{np.percentile(period, 90):.1f}; {len(slow)} of {len(period)} over "
        "1.05 x the median"
        + "".join(f", step {i} {period[i]:.1f}" for i in slow[:5]),
        f"sparse attention: {np.mean(kv) if kv else float('nan'):.2f} kv blocks "
        "of 64 a q tile in the mean (a token chose 64)",
    ]
    return result


# ``memory_analysis()`` of the step into the log, and the full list of the
# traced stretch's device operations into ``counts``.
traced_counts = routed.traced_counts
