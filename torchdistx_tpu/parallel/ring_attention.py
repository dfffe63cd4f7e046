"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context attention for sequences too large for one chip's HBM: the
sequence dim of Q/K/V is sharded over the ``sp`` mesh axis; each device keeps
its Q block resident and the K/V blocks rotate around the ring via
``lax.ppermute`` (one neighbor hop per step — the collective rides ICI), with
a numerically stable *online softmax* merging each visiting block's
contribution (the blockwise-attention recurrence of Ring Attention,
arXiv:2310.01889).  After ``sp`` steps every Q block has attended to the full
sequence; peak memory per device is O(S/sp · S/sp) logits instead of O(S²).
Causal runs skip fully-future blocks behind a ``lax.cond`` — a device
computes only its lower-triangle steps, forward and transposed backward.
Under the default *contiguous* block assignment this saves FLOPs/energy
but not wall-clock (the last device computes on every step and the
unconditional per-step ``ppermute`` keeps the ring in lockstep with it).
``schedule="zigzag"`` rebalances causal work for wall-clock too: each
device owns one *early* and one *late* half-block (device ``i`` holds
halves ``i`` and ``2n-1-i``), so every device computes exactly two
half-block contributions per ring step (three on its diagonal step) —
the per-step critical path drops from one full block to ~half.  The
zigzag sequence permutation is applied/inverted outside the ``shard_map``
(one resharding gather each way).

Implemented as ``shard_map`` over the mesh + ``lax.scan`` over ring steps, so
it nests inside the jitted train step and is reverse-differentiable (scan and
ppermute both transpose); wrap the caller in ``jax.checkpoint`` to avoid
storing per-step residuals.

The reference framework has no sequence parallelism (SURVEY.md §2.3) — this
is native new capability shaped by the TPU interconnect.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention"]

_NEG_INF = float("-inf")


def _block_contrib(q, k, v, q_off, k_off, causal):
    """One K/V block's unnormalized contribution (GQA-aware).

    q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D).  Returns
    (num (B,Sq,Hq,D) f32, m (B,Sq,Hq,1) f32, l (B,Sq,Hq,1) f32) where
    num = exp(logits - m) @ v, m = row max, l = row sum.
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    scale = 1.0 / (d**0.5)
    qg = q.reshape(b, sq, hkv, groups, d)
    logits = (
        jnp.einsum("bqhgd,bkhd->bqhgk", qg, k).astype(jnp.float32) * scale
    )  # (B, Sq, Hkv, G, Sk)
    if causal:
        qi = q_off + jnp.arange(sq)
        ki = k_off + jnp.arange(sk)
        mask = qi[:, None] >= ki[None, :]
        logits = jnp.where(mask[None, :, None, None, :], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)  # (B,Sq,Hkv,G,1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe)
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    num = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    m = jnp.where(jnp.isfinite(m), m, _NEG_INF)
    return (
        num.reshape(b, sq, hq, d),
        m.reshape(b, sq, hq, 1),
        l.reshape(b, sq, hq, 1),
    )


def _merge(acc, blk):
    """Online-softmax merge of two partial (num, m, l) triples."""
    num_a, m_a, l_a = acc
    num_b, m_b, l_b = blk
    m_new = jnp.maximum(m_a, m_b)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_a), jnp.exp(m_a - m_safe), 0.0)
    beta = jnp.where(jnp.isfinite(m_b), jnp.exp(m_b - m_safe), 0.0)
    return (num_a * alpha + num_b * beta, m_new, l_a * alpha + l_b * beta)


def _ring_body(q, k, v, *, axis: str, causal: bool):
    """Per-device body under shard_map: local blocks in, local out."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    b, sl, hq, d = q.shape
    q_off = idx * sl

    num0 = jnp.zeros((b, sl, hq, d), dtype=jnp.float32)
    m0 = jnp.full((b, sl, hq, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, sl, hq, 1), dtype=jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        k_blk, v_blk, acc = carry
        src = (idx - t) % n
        # Causal: a K/V block strictly in this Q block's future contributes
        # nothing — skip its einsums entirely (without the gate, the ring
        # wastes (n-1)/2n of its compute on all-masked blocks).  Same
        # deadlock-freedom invariant as the pipeline's tick gating: the
        # predicate varies only over the ring axis and the ppermute below
        # runs unconditionally every step.
        def visit(operand):
            k_b, v_b, acc_in = operand
            blk = _block_contrib(q, k_b, v_b, q_off, src * sl, causal)
            return _merge(acc_in, blk)

        if causal:
            acc = jax.lax.cond(
                src <= idx, visit, lambda op: op[2], (k_blk, v_blk, acc)
            )
        else:
            acc = visit((k_blk, v_blk, acc))
        k_next = jax.lax.ppermute(k_blk, axis, perm)
        v_next = jax.lax.ppermute(v_blk, axis, perm)
        return (k_next, v_next, acc), None

    (_, _, (num, m, l)), _ = jax.lax.scan(
        step, (k, v, (num0, m0, l0)), jnp.arange(n)
    )
    out = num / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def _zigzag_ring_body(q, k, v, *, axis: str):
    """Balanced causal ring body: per-device q/k/v hold halves (i, 2n-1-i).

    Case analysis per ring step visiting source block ``src`` (half indices
    ``src`` and ``2n-1-src``), against this device's halves ``idx`` and
    ``2n-1-idx``:

    * ``q_hi`` vs ``k_lo`` — ``2n-1-idx > src`` always: full, every step;
    * ``src < idx``  — ``q_lo`` vs ``k_lo`` full;
    * ``src == idx`` — both diagonal (triangular-masked) pairs;
    * ``src > idx``  — ``q_hi`` vs ``k_hi`` full
      (``2n-1-src < 2n-1-idx``);
    * ``q_lo`` vs ``k_hi`` — ``idx < 2n-1-src`` always: never computed.

    Exactly two half-contributions per step (three on the diagonal step),
    on every device — the causal load balance the contiguous assignment
    lacks.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    b, sl, hq, d = q.shape
    h = sl // 2

    def split(x):
        return x[:, :h], x[:, h:]

    q_lo, q_hi = split(q)

    def zero_acc():
        return (
            jnp.zeros((b, h, hq, d), dtype=jnp.float32),
            jnp.full((b, h, hq, 1), _NEG_INF, dtype=jnp.float32),
            jnp.zeros((b, h, hq, 1), dtype=jnp.float32),
        )

    def full(acc, qh, kh, vh):
        return _merge(acc, _block_contrib(qh, kh, vh, 0, 0, causal=False))

    def diag(acc, qh, kh, vh):
        return _merge(acc, _block_contrib(qh, kh, vh, 0, 0, causal=True))

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        k_blk, v_blk, acc_lo, acc_hi = carry
        src = (idx - t) % n
        k_lo, k_hi = split(k_blk)
        v_lo, v_hi = split(v_blk)
        acc_hi = full(acc_hi, q_hi, k_lo, v_lo)

        def before(accs):  # src strictly earlier than idx
            a_lo, a_hi = accs
            return full(a_lo, q_lo, k_lo, v_lo), a_hi

        def diagonal(accs):
            a_lo, a_hi = accs
            return (
                diag(a_lo, q_lo, k_lo, v_lo),
                diag(a_hi, q_hi, k_hi, v_hi),
            )

        def after(accs):  # src strictly later than idx
            a_lo, a_hi = accs
            return a_lo, full(a_hi, q_hi, k_hi, v_hi)

        acc_lo, acc_hi = jax.lax.switch(
            jnp.clip(jnp.sign(src - idx) + 1, 0, 2),
            [before, diagonal, after],
            (acc_lo, acc_hi),
        )
        k_next = jax.lax.ppermute(k_blk, axis, perm)
        v_next = jax.lax.ppermute(v_blk, axis, perm)
        return (k_next, v_next, acc_lo, acc_hi), None

    (_, _, (num_l, m_l, l_l), (num_h, m_h, l_h)), _ = jax.lax.scan(
        step, (k, v, zero_acc(), zero_acc()), jnp.arange(n)
    )
    out_lo = num_l / jnp.maximum(l_l, 1e-30)
    out_hi = num_h / jnp.maximum(l_h, 1e-30)
    return jnp.concatenate([out_lo, out_hi], axis=1).astype(q.dtype)


def _zigzag_perm(s: int, n: int):
    """Global seq permutation placing halves (i, 2n-1-i) on device ``i``.

    Returns ``(perm, inv)`` index vectors: ``x_zig = x[:, perm]`` and
    ``x = x_zig[:, inv]``.
    """
    import numpy as np

    if s % (2 * n):
        raise ValueError(f"zigzag needs seq {s} divisible by 2·sp={2 * n}")
    h = s // (2 * n)
    order = []
    for i in range(n):
        order.extend(range(i * h, (i + 1) * h))
        order.extend(range((2 * n - 1 - i) * h, (2 * n - i) * h))
    perm = np.asarray(order)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(s)
    return perm, inv


def ring_attention(
    q,
    k,
    v,
    *,
    mesh,
    axis: str = "sp",
    causal: bool = True,
    batch_axes: Sequence[str] = ("dp", "fsdp"),
    head_axes: Sequence[str] = ("tp",),
    schedule: str = "contiguous",
    pre_permuted: bool = False,
):
    """Sequence-parallel attention.  Layout ``(B, S, H, D)`` (global shapes).

    ``q``/``k``/``v`` are sharded ``P(batch, sp, heads, None)``; the result
    carries the same sharding.  ``batch_axes``/``head_axes`` name the mesh
    axes the batch/head dims are sharded over (entries absent from ``mesh``
    are ignored), so the shard_map composes with dp/fsdp/tp sharding without
    forcing reshards.

    ``schedule``: ``"contiguous"`` (default) or ``"zigzag"`` — the
    load-balanced causal schedule (see module docstring); requires
    ``causal=True`` and a sequence divisible by ``2·sp``.

    .. note:: zigzag permutes q/k/v in and the output back *per call*
       (four sequence-global reshards per layer, replayed in backward).
       The balance win pays when per-device attention compute dominates —
       long local sequence, large head count; for short sequences the
       reshard traffic can exceed the saving.  ``pre_permuted=True`` skips
       the per-call permutation entirely: the caller keeps the *whole
       model's* activations in zigzag sequence order (permute tokens and
       position ids once at the embedding, align the targets at the loss
       — see ``models.llama.loss_fn(seq_layout="zigzag")``), and outputs
       stay in zigzag order.
    """
    names = set(mesh.axis_names)
    if axis not in names:
        raise ValueError(f"mesh has no axis {axis!r} (axes: {mesh.axis_names})")
    batch = tuple(a for a in batch_axes if a in names) or None
    heads = tuple(a for a in head_axes if a in names) or None
    spec = P(batch, axis, heads, None)

    if schedule == "zigzag":
        if not causal:
            raise ValueError("zigzag schedule is a causal-only optimization")
        n = mesh.shape[axis]
        s = q.shape[1]
        if s % (2 * n):
            raise ValueError(
                f"zigzag needs seq {s} divisible by 2·{axis}={2 * n}"
            )
        body = functools.partial(_zigzag_ring_body, axis=axis)
        zz = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        if pre_permuted:
            return zz(q, k, v)
        perm, inv = _zigzag_perm(s, n)
        qz, kz, vz = (jnp.take(x, perm, axis=1) for x in (q, k, v))
        return jnp.take(zz(qz, kz, vz), inv, axis=1)
    if schedule != "contiguous":
        raise ValueError(f"unknown schedule: {schedule!r}")
    if pre_permuted:
        raise ValueError("pre_permuted requires schedule='zigzag'")
    body = functools.partial(_ring_body, axis=axis, causal=causal)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
