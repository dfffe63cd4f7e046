"""Lightning attention: decayed linear attention (Lightning Attention-2,
arXiv:2401.04658), the ``lightning-attn`` mixer of MiniCPM-SALA.

Per row of the batch and head ``h`` a ``(D, D)`` state runs over the
sequence, with no softmax and no denominator::

    S_t = lambda_h S_{t-1} + k_t^T v_t          o_t = q_t S_t / sqrt(D)

that is ``o_t = sum_{s <= t} lambda_h^(t-s) (q_t . k_s / sqrt(D)) v_s``.
``log_decay`` gives ``a_h = log(lambda_h) <= 0`` per head, static.

``lightning_attention(q, k, v, log_decay)``: ``q``, ``k``, ``v`` ``(Bsz, T,
H, D)`` -> ``(Bsz, T, H, D)`` in q's dtype; the state and every sum
float32.  Time runs in CHUNKS of ``chunk`` positions (the sequence is
zero-padded to a whole number of them: a zero key and value add nothing):
inside a chunk the quadratic form under the mask ``lambda^(i-j)``, across
chunks the state.  Every decay power is formed as ``lambda^n``, ``n >= 0``.

What the forward keeps: the state at each chunk's START, ``(Bsz, H,
T/chunk, D, D)`` float32, and its result; the VJP's forward rule names them
``lightning_starts`` and ``lightning_out``, and
:data:`torchdistx_tpu.ops.remat.REMAT_POLICY` saves both, so a
rematerialised block's backward goes straight to the backward kernel.  The
backward reads the start states for ``dq`` and carries the reverse state
``R = sum_{t >= c0} lambda^(t-c0) q_t^T do_t`` for ``dk`` and ``dv``.

ONE ``jax.custom_vjp`` for both implementations:

* ``impl="jnp"``: a ``lax.scan`` over chunks, the backward a reverse scan
  over ``jax.vjp`` of the chunk's function.  The CPU path, and the kernels'
  oracle.
* ``impl="pallas"``: the kernels ``lightning_fwd`` / ``lightning_bwd``
  (:mod:`torchdistx_tpu.ops.pallas.lightning_attention`), through the
  Pallas interpreter off a TPU.
* ``impl="auto"``: ``pallas`` on a TPU, else ``jnp``.

Counted per trace: ``lightning{impl=}``, ``lightning{interpret=}`` (Pallas
only), and the histogram ``lightning.chunks`` (chunks one call runs).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry as _telemetry

__all__ = ["lightning_attention", "lightning_decays", "resolve_impl"]

_CHUNK_BOUNDS = tuple(float(2**i) for i in range(17))


def lightning_decays(n_heads: int) -> tuple:
    """``log(lambda_h)`` for ``h = 0 .. n_heads - 1``: minus the ALiBi slope
    ``2^(-8 (h + 1) / n_heads)`` (Lightning Attention's, with no per-layer
    factor)."""
    return tuple(-(2.0 ** (-8.0 * (h + 1) / n_heads)) for h in range(n_heads))


# ---------------------------------------------------------------------------
# impl="jnp".  Layout inside: (n_chunks, Bsz, chunk, H, D) float32.


def _chunk_fn(s, q, k, v, a):
    """One chunk from its start state ``s (Bsz, H, D, D)``: ``q`` (already
    scaled), ``k``, ``v`` ``(Bsz, C, H, D)`` -> ``(s_end, o)``."""
    c = q.shape[1]
    i = jnp.arange(c)
    diff = (i[:, None] - i[None, :]).astype(jnp.float32)
    mask = jnp.where(
        diff >= 0, jnp.exp(a[:, None, None] * jnp.maximum(diff, 0.0)), 0.0
    )  # (H, C, C)
    pos = i.astype(jnp.float32)
    att = jnp.einsum("bihd,bjhd->bhij", q, k) * mask
    o = jnp.einsum("bhij,bjhd->bihd", att, v)
    qd = q * jnp.exp(a[None, :] * (pos[:, None] + 1.0))[None, :, :, None]
    o = o + jnp.einsum("bihd,bhde->bihe", qd, s)
    kd = k * jnp.exp(a[None, :] * (c - 1.0 - pos[:, None]))[None, :, :, None]
    s_end = s * jnp.exp(a * c)[None, :, None, None] + jnp.einsum(
        "bjhd,bjhe->bhde", kd, v
    )
    return s_end, o


def _chunked(x, chunk):
    b, t, h, d = x.shape
    return x.astype(jnp.float32).reshape(b, t // chunk, chunk, h, d).swapaxes(0, 1)


def _unchunked(x):
    n, b, c, h, d = x.shape
    return x.swapaxes(0, 1).reshape(b, n * c, h, d)


def _jnp_forward(q, k, v, a, chunk, scale):
    xs = (_chunked(q, chunk) * scale, _chunked(k, chunk), _chunked(v, chunk))
    b, _, h, d = q.shape
    s0 = jnp.zeros((b, h, d, d), jnp.float32)

    def body(s, x):
        s_end, o = _chunk_fn(s, *x, a)
        return s_end, (s, o)

    _, (starts, o) = jax.lax.scan(body, s0, xs)
    return _unchunked(o), starts.swapaxes(0, 1)  # starts (Bsz, n, H, D, D)


def _jnp_backward(q, k, v, a, starts, do, chunk, scale):
    xs = (
        starts.swapaxes(0, 1), _chunked(q, chunk) * scale,
        _chunked(k, chunk), _chunked(v, chunk), _chunked(do, chunk),
    )

    def body(ds, x):
        s, q_c, k_c, v_c, do_c = x
        _, vjp = jax.vjp(lambda *args: _chunk_fn(*args, a), s, q_c, k_c, v_c)
        ds, dq_c, dk_c, dv_c = vjp((ds, do_c))
        return ds, (dq_c * scale, dk_c, dv_c)

    _, outs = jax.lax.scan(
        body, jnp.zeros_like(starts[:, 0]), xs, reverse=True
    )
    return tuple(_unchunked(x) for x in outs)


# ---------------------------------------------------------------------------
# The differentiable entry: ``q``, ``k``, ``v`` ``(Bsz, T, H, D)`` padded to
# a whole number of chunks.  ``starts`` travel in the kernels' layout
# ``(Bsz, H, n, D, D)``.


def _forward(q, k, v, decays, chunk, impl, interpret):
    b, t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    a = jnp.asarray(decays, jnp.float32)
    if impl == "pallas":
        from .pallas import lightning_attention as kernels

        o, starts = kernels.forward(
            *(x.reshape(b, t, h * d) for x in (q, k, v)), a, heads=h,
            chunk=chunk, scale=scale, interpret=interpret,
        )
        return o.reshape(q.shape), starts
    o, starts = _jnp_forward(q, k, v, a, chunk, scale)
    return o.astype(q.dtype), starts.transpose(0, 2, 1, 3, 4)


# The primal: runs only where nothing differentiates the call.  Under
# ``jax.grad``/``jax.vjp`` JAX traces ``_la_fwd`` in its place, so a name
# given here would never reach a remat policy.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _la(q, k, v, decays, chunk, impl, interpret):
    return _forward(q, k, v, decays, chunk, impl, interpret)[0]


def _la_fwd(q, k, v, decays, chunk, impl, interpret):
    o, starts = _forward(q, k, v, decays, chunk, impl, interpret)
    # The named results are the ones the VJP returns and keeps: were the
    # saved values other variables, the recompute would keep the forward
    # kernel alive to produce them.
    o = checkpoint_name(o, "lightning_out")
    starts = checkpoint_name(starts, "lightning_starts")
    return o, (q, k, v, starts)


def _la_bwd(decays, chunk, impl, interpret, res, do):
    q, k, v, starts = res
    b, t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    a = jnp.asarray(decays, jnp.float32)
    if impl == "pallas":
        from .pallas import lightning_attention as kernels

        flat = (x.reshape(b, t, h * d) for x in (q, k, v))
        dq, dk, dv = kernels.backward(
            *flat, a, starts, do.reshape(b, t, h * d), heads=h, chunk=chunk,
            scale=scale, interpret=interpret,
        )
        return tuple(x.reshape(q.shape) for x in (dq, dk, dv))
    dq, dk, dv = _jnp_backward(
        q, k, v, a, starts.transpose(0, 2, 1, 3, 4), do, chunk, scale
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_la.defvjp(_la_fwd, _la_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """``auto`` -> ``pallas`` on a TPU, else ``jnp``; the others checked."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown lightning impl: {impl!r} (auto|jnp|pallas)")
    return impl


def lightning_attention(q, k, v, log_decay, *, impl: str = "auto",
                        chunk: int = 256):
    """Decayed linear attention (module docstring).  ``log_decay``: a
    sequence of ``H`` floats ``<= 0`` (static).  ``chunk``: positions a
    time chunk holds, a multiple of 16.  Off a TPU the kernels run through
    the Pallas interpreter."""
    impl = resolve_impl(impl)
    decays = tuple(float(x) for x in log_decay)
    if len(decays) != q.shape[2] or max(decays) > 0:
        raise ValueError(f"log_decay must give {q.shape[2]} values <= 0")
    if chunk % 16:
        raise ValueError(f"chunk must be a multiple of 16, got {chunk}")
    t = q.shape[1]
    chunk = min(chunk, -(-t // 16) * 16)
    n = -(-t // chunk)
    _telemetry.counter("lightning", impl=impl).add()
    _telemetry.histogram("lightning.chunks", _CHUNK_BOUNDS).observe(n)
    interpret = impl == "pallas" and not _on_tpu()
    if impl == "pallas":
        _telemetry.counter("lightning", interpret=str(interpret).lower()).add()
    pad = n * chunk - t
    if pad:
        with jax.named_scope("relayout"):
            q, k, v = (
                jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v)
            )
    o = _la(q, k, v, decays, chunk, impl, interpret)
    return o[:, :t] if pad else o
