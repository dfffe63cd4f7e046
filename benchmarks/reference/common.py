"""What the plain references share: causal attention in query blocks,
cross-entropy, and the precision rule.

The reference is float32 under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes).  The same code in
bfloat16 at default precision measures bf16's own floor for a depth, width
and context (``reference/measure_tol.py``): statistics of norms and softmax
stay float32 there, as in any bf16 implementation.
"""

import contextlib

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def precision(dtype):
    if jnp.dtype(dtype) == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def causal_attention(q, k, v):
    """``q (B,T,Hkv,G,Dh)``, ``k``/``v (B,T,Hkv,Dh)`` -> ``(B,T,Hkv*G*Dh)``.
    Query rows go through in blocks of ``Q_BLOCK`` so the score matrix of a
    4096-position stream never exists whole."""
    b, t, hkv, g, dh = q.shape
    pos = jnp.arange(t)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(dh))
        mask = pos[None, :] <= pos[lo:lo + Q_BLOCK, None]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
        outs.append(o.reshape(b, qb.shape[1], hkv * g * dh))
    return jnp.concatenate(outs, axis=1)


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
