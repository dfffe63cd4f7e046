"""The selective scan of a state-space (Mamba-1) mixer.

For every row of the batch and every channel ``c`` a state of ``N`` floats
runs over the sequence::

    h_0 = 0
    h_t = exp(delta_t[c] * A[c]) * h_{t-1} + delta_t[c] * u_t[c] * B_t
    y_t[c] = h_t . C_t + D[c] * u_t[c]

``A`` is per channel and state, ``B_t`` and ``C_t`` per position (shared by
the channels), ``delta_t`` per position and channel: the recurrence is
input-dependent in every factor, so it is no convolution and no matrix
product, and its ``(T, C, N)`` states are never materialised.

``selective_scan(u, delta, A, B, C, D)``:

========  ===========  ======================================================
argument  shape        dtype
========  ===========  ======================================================
``u``     (Bsz, T, C)  the model's (bfloat16 in training)
``delta`` (Bsz, T, C)  the model's; already positive (softplus applied)
``A``     (C, N)       float32, negative (``-exp(A_log)``)
``B``     (Bsz, T, N)  the model's
``C``     (Bsz, T, N)  the model's
``D``     (C,)         any float; the skip ``D * u``
result    (Bsz, T, C)  ``u``'s
========  ===========  ======================================================

The state, ``exp`` and every sum are float32 whatever the arguments'
dtype.  What lives where: the ``D * u`` skip is inside (``u`` is read
anyway, so it costs no bytes), ``delta``'s softplus and the ``silu(z)``
gate are the caller's (XLA fuses the first into ``dt_proj``'s epilogue and
the second into the pass that feeds ``out_proj``; inside the kernel they
would save one ``(T, C)`` read each, 0.1 ms of a 5 ms layer, and put two
more operands in VMEM).

Time runs in CHUNKS of ``chunk`` positions (the sequence is zero-padded to
a whole number of them: ``delta = 0`` leaves the state as it is).  The
forward keeps the state at each chunk's start, ``(Bsz, T/chunk, N, C)``
float32 — 21 MB a layer at 8,192 x 5,120 x 16 in chunks of 128, where all
states would be 2.7 GB — and the backward walks the chunks in reverse,
recomputes a chunk's states from its start and carries ``dh`` across.

What the forward keeps, and for whom.  For its own backward: the six
arguments and the chunk-start states.  For a rematerialised block around
it: the VJP's forward rule names its two results, ``ssm_out`` (``y``, the
model's dtype) and ``ssm_starts`` (the chunk-start states, float32), and
:data:`torchdistx_tpu.ops.remat.REMAT_POLICY` saves both, so the block's
backward recomputes the scan's ARGUMENTS (the projections, convolution and
norms before it) and never the scan: the forward runs once a layer.  (The
backward rule itself never reads ``y``; the gate and ``out_proj`` after
the scan do.)  The bytes that costs a layer are in ``ops/remat.py``.

ONE ``jax.custom_vjp`` for both implementations, so both keep the same two
arrays:

* ``impl="jnp"``: a ``lax.scan`` over chunks of a ``lax.scan`` over
  positions, the backward ``jax.vjp`` of the chunk's function.  The CPU
  path, and the kernels' oracle.
* ``impl="pallas"``: the kernel pair ``ssm_scan_fwd`` / ``ssm_scan_bwd``
  (:mod:`torchdistx_tpu.ops.pallas.selective_scan`), through the Pallas
  interpreter off a TPU.
* ``impl="auto"``: ``pallas`` on a TPU, else ``jnp``.

Counted per trace: ``ssm.scan{impl=}``, ``ssm.scan{interpret=}`` (Pallas
only) and the histogram ``ssm.scan_chunks`` (chunks one call runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry as _telemetry

__all__ = ["selective_scan", "resolve_impl"]

_CHUNK_BOUNDS = tuple(float(2**i) for i in range(17))


def _chunks(t: int, chunk: int):
    """``(chunk, n_chunks)`` for a sequence of ``t``: a short sequence is
    one chunk of its own length up to a multiple of 16 (a bfloat16 tile)."""
    chunk = min(chunk, -(-t // 16) * 16)
    return chunk, -(-t // chunk)


# ---------------------------------------------------------------------------
# impl="jnp": plain scans.  Layout inside: time-major, state (Bsz, C, N).


def _chunk_fn(h, u, dt, a, b, c):
    """One chunk from its start state: ``u``/``dt (chunk, Bsz, C)``,
    ``b``/``c (chunk, Bsz, N)`` float32 -> ``(h_end, y (chunk, Bsz, C))``
    without the skip."""

    def step(h, x):
        u_t, dt_t, b_t, c_t = x
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, (h * c_t[:, None, :]).sum(-1)

    return jax.lax.scan(step, h, (u, dt, b, c))


def _time_major(x, chunk, n):
    """``(Bsz, T_pad, W)`` -> ``(n, chunk, Bsz, W)`` float32."""
    bsz, _, w = x.shape
    return x.astype(jnp.float32).reshape(bsz, n, chunk, w).transpose(1, 2, 0, 3)


def _batch_major(x):
    n, chunk, bsz, w = x.shape
    return x.transpose(2, 0, 1, 3).reshape(bsz, n * chunk, w)


def _jnp_forward(u, dt, a, b, c, chunk, n):
    xs = tuple(_time_major(x, chunk, n) for x in (u, dt, b, c))
    h0 = jnp.zeros((u.shape[0], u.shape[2], a.shape[1]), jnp.float32)

    def body(h, x):
        h_end, y = _chunk_fn(h, *x[:2], a, *x[2:])
        return h_end, (h, y)

    _, (starts, y) = jax.lax.scan(body, h0, xs)
    # The kernels' layout of the chunk-start states: (Bsz, n, N, C).
    return _batch_major(y), starts.transpose(1, 0, 3, 2)


def _jnp_backward(u, dt, a, b, c, starts, dy, chunk, n):
    xs = tuple(_time_major(x, chunk, n) for x in (u, dt, b, c, dy))
    starts = starts.transpose(1, 0, 3, 2)  # (n, Bsz, C, N)

    def body(carry, x):
        dh, da = carry
        h, u_c, dt_c, b_c, c_c, dy_c = x
        _, vjp = jax.vjp(_chunk_fn, h, u_c, dt_c, a, b_c, c_c)
        dh, du_c, ddt_c, da_c, db_c, dc_c = vjp((dh, dy_c))
        return (dh, da + da_c), (du_c, ddt_c, db_c, dc_c)

    zeros = jnp.zeros(starts.shape[1:], jnp.float32)
    (_, da), outs = jax.lax.scan(
        body, (zeros, jnp.zeros_like(a)), (starts,) + xs, reverse=True
    )
    du, ddt, db, dc = (_batch_major(x) for x in outs)
    return du, ddt, da, db, dc


# ---------------------------------------------------------------------------
# The differentiable entry.  ``u``, ``delta``, ``B``, ``C`` arrive padded to
# a whole number of chunks; ``A`` float32.


def _forward(u, dt, a, b, c, d, chunk, impl, interpret):
    if impl == "pallas":
        from .pallas import selective_scan as kernels

        return kernels.forward(u, dt, a, b, c, d, chunk=chunk, interpret=interpret)
    y, starts = _jnp_forward(u, dt, a, b, c, chunk, u.shape[1] // chunk)
    y = y + d.astype(jnp.float32) * u.astype(jnp.float32)
    return y.astype(u.dtype), starts


# The primal: runs only where nothing differentiates the call.  Under
# ``jax.grad``/``jax.vjp`` JAX traces ``_scan_fwd`` in its place, so a name
# given here would never reach a remat policy.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(u, dt, a, b, c, d, chunk, impl, interpret):
    return _forward(u, dt, a, b, c, d, chunk, impl, interpret)[0]


def _scan_fwd(u, dt, a, b, c, d, chunk, impl, interpret):
    y, starts = _forward(u, dt, a, b, c, d, chunk, impl, interpret)
    # The forward's two results carry the names ``ops.remat.REMAT_POLICY``
    # saves.  The named ``y`` is the VJP's result and the named ``starts``
    # the residual: were either another variable than the saved one, the
    # recompute would keep the forward kernel alive to produce it.
    y = checkpoint_name(y, "ssm_out")
    starts = checkpoint_name(starts, "ssm_starts")
    return y, (u, dt, a, b, c, d, starts)


def _scan_bwd(chunk, impl, interpret, res, dy):
    u, dt, a, b, c, d, starts = res
    if impl == "pallas":
        from .pallas import selective_scan as kernels

        du, ddt, da, db, dc, dd = kernels.backward(
            u, dt, a, b, c, d, starts, dy, chunk=chunk, interpret=interpret
        )
    else:
        du, ddt, da, db, dc = _jnp_backward(
            u, dt, a, b, c, starts, dy, chunk, u.shape[1] // chunk
        )
        dyf, uf = dy.astype(jnp.float32), u.astype(jnp.float32)
        du = du + d.astype(jnp.float32) * dyf
        dd = (dyf * uf).sum((0, 1))
    with jax.named_scope("relayout"):
        return (
            du.astype(u.dtype), ddt.astype(dt.dtype), da.astype(a.dtype),
            db.astype(b.dtype), dc.astype(c.dtype), dd.astype(d.dtype),
        )


_scan.defvjp(_scan_fwd, _scan_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """``auto`` -> ``pallas`` on a TPU, else ``jnp``; the others checked."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown selective_scan impl: {impl!r} (auto|jnp|pallas)")
    return impl


def selective_scan(u, delta, A, B, C, D, *, impl: str = "auto",
                   chunk: int = 128):
    """The selective scan (module docstring).  ``chunk``: positions a time
    chunk holds, a multiple of 16.  Off a TPU the kernels run through the
    Pallas interpreter."""
    impl = resolve_impl(impl)
    if chunk % 16:
        raise ValueError(f"chunk must be a multiple of 16, got {chunk}")
    t = u.shape[1]
    chunk, n = _chunks(t, chunk)
    _telemetry.counter("ssm.scan", impl=impl).add()
    _telemetry.histogram("ssm.scan_chunks", _CHUNK_BOUNDS).observe(n)
    interpret = impl == "pallas" and not _on_tpu()
    if impl == "pallas":
        _telemetry.counter("ssm.scan", interpret=str(interpret).lower()).add()
    pad = n * chunk - t
    with jax.named_scope("relayout"):
        if pad:
            # delta = 0: the state passes unchanged, and the rows are cut off.
            u, delta, B, C = (
                jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                for x in (u, delta, B, C)
            )
        A = A.astype(jnp.float32)
    y = _scan(u, delta, A, B, C, D, chunk, impl, interpret)
    with jax.named_scope("relayout"):
        return y[:, :t] if pad else y
