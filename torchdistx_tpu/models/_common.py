"""What the families share.  For now the row-blocked loss head
(:func:`blocked_head_ce`), which the families whose logits do not fit
whole call: ``smallthinker`` (untied, ``(D, V)``) and ``jamba`` (tied,
the embedding's ``(V, D)``).

The head takes its gradient in the forward pass.  Per block of rows, while
the block's logits are live, it forms their cotangent as the autodiff of
the plain loss forms it (``jax.vjp`` of the block's cross-entropy sum at
``1 / n``) and runs the two products that take it back to ``h`` and to the
table: three vocabulary-wide products a block, one loop.  Under autodiff
with ``jax.checkpoint`` the backward replayed the logits product for a
fourth.  What is kept for the backward is ``dh (n, D)`` and ``dW``, in the
parameters' dtypes; nothing the size of a block's logits.  The backward
scales them by the incoming cotangent and runs no product.

Counters: ``head.ce{grad=forward}`` a trace of the forward rule, and the
histogram ``head.row_blocks`` (blocks that trace runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry

__all__ = ["blocked_head_ce"]

# Rows of the flattened batch the head takes at a time.
_HEAD_ROWS = 4096
_BLOCK_BOUNDS = tuple(float(2**i) for i in range(11))


def _logits(hb, w, vocab_major):
    if vocab_major:
        return jnp.einsum("rd,vd->rv", hb, w)
    return hb @ w


def _ce_sum(logits, tb):
    """The block's summed cross-entropy: float32 log-sum-exp over logits
    of any float dtype, less the target's logit."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
    return (lse - tgt.astype(jnp.float32)).sum()


def _blocks(h, targets, rows):
    n = h.shape[0]
    size = rows if n % rows == 0 else n
    return size, (h.reshape(-1, size, h.shape[-1]), targets.reshape(-1, size))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head_ce(h, w, targets, vocab_major, rows):
    size, blocks = _blocks(h, targets, rows)

    def block(total, xs):
        hb, tb = xs
        return total + _ce_sum(_logits(hb, w, vocab_major), tb), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), blocks)
    return total / h.shape[0]


def _head_ce_fwd(h, w, targets, vocab_major, rows):
    n = h.shape[0]
    size, blocks = _blocks(h, targets, rows)
    _telemetry.counter("head.ce", grad="forward").add()
    _telemetry.histogram("head.row_blocks", _BLOCK_BOUNDS).observe(n // size)
    # The cotangent ``total / n`` hands each block's sum under autodiff.
    scale = jnp.float32(1.0) / n
    # dh = dlogits wᵀ and dW = hᵀ dlogits, contracted where the table lies.
    dh_dims = (((1,), (0,)) if vocab_major else ((1,), (1,)), ((), ()))

    def block(carry, xs):
        total, dw = carry
        hb, tb = xs
        part, vjp = jax.vjp(
            lambda l: _ce_sum(l, tb), _logits(hb, w, vocab_major)
        )
        (dl,) = vjp(scale)
        dhb = jax.lax.dot_general(dl, w, dh_dims)
        if vocab_major:
            dwb = jax.lax.dot_general(dl, hb, (((0,), (0,)), ((), ())))
        else:
            dwb = jax.lax.dot_general(hb, dl, (((0,), (0,)), ((), ())))
        return (total + part, dw + dwb), dhb

    (total, dw), dh = jax.lax.scan(
        block, (jnp.zeros((), jnp.float32), jnp.zeros_like(w)), blocks
    )
    return total / n, (dh.reshape(h.shape).astype(h.dtype), dw.astype(w.dtype))


def _head_ce_bwd(vocab_major, rows, res, g):
    dh, dw = res
    return (g * dh).astype(dh.dtype), (g * dw).astype(dw.dtype), None


_head_ce.defvjp(_head_ce_fwd, _head_ce_bwd)


def blocked_head_ce(h, w, targets, *, vocab_major: bool):
    """Mean next-token cross-entropy of ``h (n, D)`` through the head ``w``
    (``(D, V)``, or ``(V, D)`` with ``vocab_major``, read where it lies),
    ``_HEAD_ROWS`` rows at a time (``n`` whole when they do not divide
    it): ``llama._head_ce``'s numbers, the logits in ``h``'s and ``w``'s
    dtype and the log-sum-exp in float32, without ever holding the ``(n,
    V)`` logits whole.  ``targets (n,)`` int."""
    return _head_ce(h, w, targets, vocab_major, _HEAD_ROWS)
