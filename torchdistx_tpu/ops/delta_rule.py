"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464): the
``linear_attention`` mixer of Olmo-Hybrid, linear attention whose state is
corrected towards each new value and decays by a gate that the data sets.

Per row of the batch and head a ``(d_k, d_v)`` float32 state runs over the
sequence::

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``q``, ``k`` come in normalised and scaled (the layer's L2 norm and
``d_k^-1/2`` are the model's), ``g_t <= 0`` and ``beta_t`` (in ``[0, 2]``
where the transition may have a negative eigenvalue) per head.

``gated_delta_rule(q, k, v, g, beta)``: ``q``, ``k`` ``(Bsz, T, H, d_k)``,
``v`` ``(Bsz, T, H, d_v)``, ``g``, ``beta`` ``(Bsz, T, H)`` float32 ->
``(Bsz, T, H, d_v)`` in v's dtype; the state and every sum float32.  Time
runs in CHUNKS of ``chunk`` positions (the sequence is zero-padded to a
whole number of them: a zero key with a zero ``beta`` and ``g`` leaves the
state as it was).  Inside a chunk, with ``G`` the chunk's cumulative sum
of ``g`` and ``Gamma_ij = exp(G_i - G_j)`` for ``j <= i``, the WY form::

    A = strictly_lower(diag(beta) (K K^T) * Gamma)      T = (I + A)^-1
    W = T diag(beta) (K * e^G)                          U = T diag(beta) V
    V' = U - W S                                        (the corrected values)
    O = (Q * e^G) S + (Q K^T * Gamma) V'
    S <- e^{G_C} S + (K * e^{G_C - G})^T V'

Every decay factor is ``exp`` of a sum of gates, ``<= 0``, never the
quotient of two cumulative products: a fast gate's factors underflow to 0,
none overflows.  The gates' gradient is taken over the positions each
exponent spans (``sum_{j < m <= i} g_m``), not through the cumulative sum:
through ``G_i - G_j`` the shares of the pairs on one side of a position
cancel, terms far larger than what is left, and a head's ``A_log``
gradient came out some 1e-3 off (``tests/test_delta_rule.py``).

What the forward keeps: the state at each chunk's START, ``(Bsz, H,
T/chunk, d_k, d_v)`` float32, and its result; the VJP's forward rule names
them ``delta_starts`` and ``delta_out``, and
:data:`torchdistx_tpu.ops.remat.REMAT_POLICY` saves both, so a
rematerialised block's backward goes straight to the backward kernel,
which walks the chunks in reverse carrying ``dS``.

ONE ``jax.custom_vjp`` for both implementations:

* ``impl="jnp"``: a ``lax.scan`` over chunks, ``T`` by a triangular solve,
  the backward a reverse scan over ``jax.vjp`` of the chunk's function.
  The CPU path, and the kernels' oracle.
* ``impl="pallas"``: the kernels ``delta_rule_fwd`` / ``delta_rule_bwd``
  (:mod:`torchdistx_tpu.ops.pallas.delta_rule`), through the Pallas
  interpreter off a TPU.  Every device operation of the chunk work runs
  inside them, the cumulative gate and the inverse included.
* ``impl="auto"``: ``pallas`` on a TPU, else ``jnp``.

The choices the kernels make (chunk, inverse, layout) are in
:mod:`torchdistx_tpu.ops.pallas.delta_rule`'s docstring with their reasons.

Counted per trace: ``delta_rule{impl=}``, ``delta_rule{interpret=}``
(Pallas only), and the histogram ``delta_rule.chunks`` (chunks one call
runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry as _telemetry

__all__ = ["gated_delta_rule", "recurrent_delta_rule", "resolve_impl"]

_CHUNK_BOUNDS = tuple(float(2**i) for i in range(17))


def recurrent_delta_rule(q, k, v, g, beta):
    """The per-token recurrence itself (module docstring), one state a
    position: ``lax.scan`` over ``T``, float32.  For tests at small sizes."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        kv = jnp.einsum("bhk,bhkv->bhv", k_t, s)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, (v_t - kv) * b_t[..., None])
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    xs = tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, s0, xs)
    return o.swapaxes(0, 1)


# ---------------------------------------------------------------------------
# impl="jnp".  Layout inside: (n_chunks, Bsz, H, chunk, D) float32.


def _chunk_fn(s, q, k, v, g, beta):
    """One chunk from its start state ``s (Bsz, H, d_k, d_v)``: ``q``, ``k``
    ``(Bsz, H, C, d_k)``, ``v (Bsz, H, C, d_v)``, ``g``, ``beta`` ``(Bsz, H,
    C)`` -> ``(s_end, o)``.

    Every decay exponent is the sum of the gates it spans, ``sum_{j < m <=
    i} g_m`` and ``sum_{m > i} g_m``, not a difference of cumulative sums:
    the gradient of such a difference cancels the gates' shares between
    positions it does not span, terms far larger than what is left."""
    c = q.shape[-2]
    i = jnp.arange(c)
    lower = i[:, None] >= i[None, :]
    strict = i[:, None] > i[None, :]
    m = i[None, None, :]
    spans = (i[None, :, None] < m) & (m <= i[:, None, None])  # [i, j, m]
    seg = jnp.einsum("ijm,bhm->bhij", spans.astype(g.dtype), g)
    gamma = jnp.where(lower, jnp.exp(seg), 0.0)
    kk = jnp.einsum("bhid,bhjd->bhij", k, k)
    a = jnp.where(strict, beta[..., :, None] * kk * gamma, 0.0)
    eg = jnp.exp(jnp.cumsum(g, axis=-1))[..., None]
    kb = beta[..., None] * k * eg
    vb = beta[..., None] * v
    tri = a + jnp.eye(c, dtype=a.dtype)
    w, u = (
        jax.lax.linalg.triangular_solve(
            tri, x, left_side=True, lower=True, unit_diagonal=True
        )
        for x in (kb, vb)
    )
    corrected = u - w @ s
    p = jnp.einsum("bhid,bhjd->bhij", q, k) * gamma
    o = (q * eg) @ s + p @ corrected
    after = jnp.einsum("im,bhm->bhi", strict.T.astype(g.dtype), g)  # m > i
    kd = k * jnp.exp(after)[..., None]
    s_end = jnp.exp(jnp.sum(g, axis=-1))[..., None, None] * s + jnp.einsum(
        "bhck,bhcv->bhkv", kd, corrected
    )
    return s_end, o


def _chunked(x, chunk):
    """``(Bsz, H, T, ...)`` -> ``(n, Bsz, H, chunk, ...)`` float32."""
    b, h, t = x.shape[:3]
    x = x.astype(jnp.float32).reshape(b, h, t // chunk, chunk, *x.shape[3:])
    return jnp.moveaxis(x, 2, 0)


def _unchunked(x):
    n, b, h, c = x.shape[:4]
    return jnp.moveaxis(x, 0, 2).reshape(b, h, n * c, *x.shape[4:])


def _jnp_forward(q, k, v, g, beta, chunk):
    xs = tuple(_chunked(x, chunk) for x in (q, k, v, g, beta))
    b, h, _, dk = q.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def body(s, x):
        s_end, o = _chunk_fn(s, *x)
        return s_end, (s, o)

    _, (starts, o) = jax.lax.scan(body, s0, xs)
    return _unchunked(o), jnp.moveaxis(starts, 0, 2)


def _jnp_backward(q, k, v, g, beta, starts, do, chunk):
    xs = (jnp.moveaxis(starts, 2, 0),) + tuple(
        _chunked(x, chunk) for x in (q, k, v, g, beta, do)
    )

    def body(ds, x):
        s, *ins, do_c = x
        _, vjp = jax.vjp(_chunk_fn, s, *ins)
        ds, *grads = vjp((ds, do_c))
        return ds, tuple(grads)

    _, outs = jax.lax.scan(
        body, jnp.zeros_like(starts[:, :, 0]), xs, reverse=True
    )
    return tuple(_unchunked(x) for x in outs)


# ---------------------------------------------------------------------------
# The differentiable entry, in the kernels' layout: ``q``, ``k`` ``(Bsz, H,
# T, d_k)``, ``v (Bsz, H, T, d_v)``, ``g``, ``beta`` ``(Bsz, H, T)``
# float32, ``T`` a whole number of chunks; ``starts (Bsz, H, n, d_k, d_v)``.


def _forward(q, k, v, g, beta, chunk, impl, interpret):
    if impl == "pallas":
        from .pallas import delta_rule as kernels

        return kernels.forward(q, k, v, g, beta, chunk=chunk, interpret=interpret)
    o, starts = _jnp_forward(q, k, v, g, beta, chunk)
    return o.astype(v.dtype), starts


# The primal: runs only where nothing differentiates the call.  Under
# ``jax.grad``/``jax.vjp`` JAX traces ``_dr_fwd`` in its place, so a name
# given here would never reach a remat policy.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _dr(q, k, v, g, beta, chunk, impl, interpret):
    return _forward(q, k, v, g, beta, chunk, impl, interpret)[0]


def _dr_fwd(q, k, v, g, beta, chunk, impl, interpret):
    o, starts = _forward(q, k, v, g, beta, chunk, impl, interpret)
    # The named results are the ones the VJP returns and keeps: were the
    # saved values other variables, the recompute would keep the forward
    # kernel alive to produce them.
    o = checkpoint_name(o, "delta_out")
    starts = checkpoint_name(starts, "delta_starts")
    return o, (q, k, v, g, beta, starts)


def _dr_bwd(chunk, impl, interpret, res, do):
    q, k, v, g, beta, starts = res
    if impl == "pallas":
        from .pallas import delta_rule as kernels

        return kernels.backward(
            q, k, v, g, beta, starts, do, chunk=chunk, interpret=interpret
        )
    grads = _jnp_backward(q, k, v, g, beta, starts, do, chunk)
    return tuple(d.astype(x.dtype) for d, x in zip(grads, (q, k, v, g, beta)))


_dr.defvjp(_dr_fwd, _dr_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """``auto`` -> ``pallas`` on a TPU, else ``jnp``; the others checked."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown delta rule impl: {impl!r} (auto|jnp|pallas)")
    return impl


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 128, impl: str = "auto",
                     interpret=None):
    """The gated delta rule (module docstring).  ``chunk``: positions a
    time chunk holds, a multiple of 8.  ``interpret``: run the kernels
    through the Pallas interpreter (default: off a TPU)."""
    impl = resolve_impl(impl)
    if chunk % 8:
        raise ValueError(f"chunk must be a multiple of 8, got {chunk}")
    t = q.shape[1]
    chunk = min(chunk, -(-t // 8) * 8)
    n = -(-t // chunk)
    if interpret is None:
        interpret = not _on_tpu()
    interpret = bool(interpret) and impl == "pallas"
    _telemetry.counter("delta_rule", impl=impl).add()
    _telemetry.histogram("delta_rule.chunks", _CHUNK_BOUNDS).observe(n)
    if impl == "pallas":
        _telemetry.counter("delta_rule", interpret=str(interpret).lower()).add()
    pad = n * chunk - t
    with jax.named_scope("relayout"):
        heads_first = [
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).swapaxes(1, 2)
            for x in (q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32))
        ]
    o = _dr(*heads_first, chunk, impl, interpret)
    with jax.named_scope("relayout"):
        o = o.swapaxes(1, 2)
    return o[:, :t] if pad else o
