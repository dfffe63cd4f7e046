"""The gated delta rule — Pallas TPU kernels ``delta_rule_fwd`` and
``delta_rule_bwd``.

The recurrence, its chunk form and its arguments are
:mod:`torchdistx_tpu.ops.delta_rule`'s.  The grid is ``(row, head, time
chunk)``, the chunks sequential; a head's ``(d_k, d_v)`` float32 state (the
backward: its cotangent) stays in VMEM scratch from one chunk to the next.

Layout.  ``q``, ``k`` ``(Bsz, H, T, d_k)`` and ``v``, ``o`` ``(Bsz, H, T,
d_v)``: a block is a head's ``(chunk, d)``, whole in its last dimension, so
widths that are not multiples of the 128 lanes (Olmo-Hybrid's 96 and 192)
need no padding in HBM.  ``g`` and ``beta`` ``(Bsz, H, T/chunk, chunk)``:
a row's whole gate sits in VMEM (64 KiB at 16,384 positions) and a step
reads its chunk's row of it; the backward writes ``dg`` and ``dbeta`` the
same way.  Inside a step the per-position scalars are needed both down the
sublanes (scaling the rows of ``K``) and along the lanes (``Gamma_ij``):
the column is taken from the row by a masked lane reduction, no transpose.

The inverse ``T = (I + A)^-1`` of the unit lower-triangular chunk matrix is
the product ``(I - A)(I + A^2)(I + A^4) ... (I + A^(C/2))`` (``A`` is
nilpotent: ``A^C = 0``): ``2 log2(C) - 2`` products of ``(C, C)`` matrices
on the MXU, where forward substitution would be ``C`` dependent steps of a
row each on the vector unit.

The chunk.  Olmo-Hybrid's cell runs chunks of 128: on a v5e, 30 heads of
96 / 192 over 16,384 positions, forward and backward with the relayouts
take 51.0 ms at 128 against 59.0 at 64 (the forward alone 19.0 against
21.9), the tiles filling the 128-wide MXU; and the chunk-start states a
layer keeps are half as many, 0.28 GB against 0.57, which the step's
memory needs (``peak_memory_in_bytes`` 14.72 GiB against 15.14 of 15.75,
compile-only).

Precision.  The products of the operands as they come (``K K^T``, ``Q
K^T``, bfloat16 in training) take them as they are with float32
accumulation; every product that involves the state, the inverse or the
corrected values takes float32 operands at ``HIGHEST``, so nothing of the
recurrence is rounded below float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["forward", "backward"]

_VMEM_LIMIT = 64 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _dot(a, b, contract=((1,), (0,))):
    """``a . b`` over the dims ``contract`` names, float32 accumulation;
    HIGHEST where an operand is float32."""
    hi = _HI if _F32 in (a.dtype, b.dtype) else None
    if hi is not None:
        a, b = a.astype(_F32), b.astype(_F32)
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=_F32, precision=hi
    )


_T = ((0,), (0,))  # a^T b
_BT = ((1,), (1,))  # a b^T


def _iota(c):
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return i, j


def _column(row, i, j):
    """A ``(1, C)`` row as a ``(C, 1)`` column."""
    c = row.shape[1]
    return jnp.sum(jnp.where(i == j, jnp.broadcast_to(row, (c, c)), 0.0),
                   axis=1, keepdims=True)


def _row(col, i, j):
    """A ``(C, 1)`` column as a ``(1, C)`` row."""
    c = col.shape[0]
    return jnp.sum(jnp.where(i == j, jnp.broadcast_to(col, (c, c)), 0.0),
                   axis=0, keepdims=True)


def _inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a (C, C)``: after
    the loop ``(I + a) t = I - a^n`` with ``n >= C``, and ``a^C = 0``."""
    c = a.shape[0]
    i, j = _iota(c)
    t = jnp.where(i == j, 1.0, 0.0) - a
    p, n = a, 2
    while n < c:
        p = _dot(p, p)
        t = t + _dot(t, p)
        n *= 2
    return t


def _chunk(g_row, b_row, k):
    """What both kernels rebuild of a chunk: ``(i, j, cum, beta, gamma,
    kk, a, t)`` — the cumulative gate as a column, beta as a column, the
    decay mask (``j <= i``), ``K K^T``, ``A`` and ``T = (I + A)^-1``."""
    c = g_row.shape[1]
    i, j = _iota(c)
    cum = jnp.sum(jnp.where(j <= i, jnp.broadcast_to(g_row, (c, c)), 0.0),
                  axis=1, keepdims=True)  # (C, 1): G_i = sum_{j <= i} g_j
    cum_row = _row(cum, i, j)
    beta = _column(b_row, i, j)
    lower = j <= i
    gamma = jnp.where(lower, jnp.exp(jnp.where(lower, cum - cum_row, 0.0)), 0.0)
    kk = _dot(k, k, _BT)
    a = jnp.where(j < i, beta * kk * gamma, 0.0)
    return i, j, cum, beta, gamma, kk, a, _inverse(a)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_out_ref, s_scr):
    import jax.experimental.pallas as pl

    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    g_row = g_ref[0, 0, pl.ds(c, 1), :]
    b_row = b_ref[0, 0, pl.ds(c, 1), :]
    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    s = s_scr[...]
    s_out_ref[0, 0, 0] = s
    _, _, cum, beta, gamma, _, _, t = _chunk(g_row, b_row, k)
    kf, eg = k.astype(_F32), jnp.exp(cum)
    w = _dot(t, beta * kf * eg)
    u = _dot(t, beta * v.astype(_F32))
    corrected = u - _dot(w, s)
    p = _dot(q, k, _BT) * gamma
    o = _dot(q.astype(_F32) * eg, s) + _dot(p, corrected)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    last = jnp.sum(g_row, axis=1, keepdims=True)  # (1, 1): G_C
    s_scr[...] = jnp.exp(last) * s + _dot(kf * jnp.exp(last - cum), corrected, _T)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, last_chunk):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)  # the row's LAST chunk
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    c = last_chunk - pl.program_id(2)
    g_row = g_ref[0, 0, pl.ds(c, 1), :]
    b_row = b_ref[0, 0, pl.ds(c, 1), :]
    q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
    s, ds_end = s_ref[0, 0, 0], ds_scr[...]
    i, j, cum, beta, gamma, kk, a, t = _chunk(g_row, b_row, k)
    qf, kf, vf, dof = (x.astype(_F32) for x in (q, k, v, do))
    eg = jnp.exp(cum)
    last = jnp.sum(g_row, axis=1, keepdims=True)  # (1, 1): G_C
    e_last, e_rest = jnp.exp(last), jnp.exp(last - cum)
    kb, vb = beta * kf * eg, beta * vf
    w = _dot(t, kb)
    corrected = _dot(t, vb) - _dot(w, s)
    qk = _dot(q, k, _BT)
    p = qk * gamma
    kd = kf * e_rest
    # Through S_end = e^{G_C} S + Kd^T V' and O = (Q e^G) S + P V'.
    d_corr = _dot(kd, ds_end) + _dot(p, dof, _T)
    d_kd = _dot(corrected, ds_end, _BT)
    d_qg = _dot(dof, s, _BT)
    d_p = _dot(dof, corrected, _BT)
    ds_scr[...] = (
        e_last * ds_end + _dot(qf * eg, dof, _T) - _dot(w, d_corr, _T)
    )
    # Through V' = T Vb - T Kb S and T = (I + A)^-1.
    d_w = -_dot(d_corr, s, _BT)
    d_t = _dot(d_corr, vb, _BT) + _dot(d_w, kb, _BT)
    d_vb, d_kb = _dot(t, d_corr, _T), _dot(t, d_w, _T)
    d_a = jnp.where(j < i, -_dot(_dot(t, d_t, _T), t, _BT), 0.0)
    m = jnp.where(j < i, kk * gamma, 0.0)  # A = diag(beta) M
    dv_ref[0, 0] = (beta * d_vb).astype(dv_ref.dtype)
    d_beta = (
        jnp.sum(d_vb * vf, axis=1, keepdims=True)
        + jnp.sum(d_kb * kf * eg, axis=1, keepdims=True)
        + jnp.sum(d_a * m, axis=1, keepdims=True)
    )
    d_m = beta * d_a * gamma  # the cotangent of K K^T through A
    d_pg = d_p * gamma  # and through P
    dk = (
        beta * eg * d_kb + _dot(d_m, kf) + _dot(d_m, kf, _T)
        + _dot(d_pg, qf, _T) + d_kd * e_rest
    )
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dq = _dot(d_pg, kf) + d_qg * eg
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    # The gates: g_m enters e^{G_i} for i >= m, e^{G_C - G_i} for i < m,
    # e^{G_C} everywhere and Gamma_ij where j < m <= i.  Each sum is taken
    # over what it spans: through the cumulative G, the shares of the pairs
    # on one side of m cancel, terms far larger than what is left.
    n = g_row.shape[1]
    e = d_m * kk + d_pg * qk
    spanned = _dot(e, jnp.where(i < j, 1.0, 0.0))  # [i, m] = sum_{j < m} e_ij
    after = jnp.sum(d_kb * kb + d_qg * qf * eg, axis=1, keepdims=True)
    before = jnp.sum(d_kd * kd, axis=1, keepdims=True)
    d_all = e_last * jnp.sum(
        jnp.sum(ds_end * s, axis=1, keepdims=True), axis=0, keepdims=True
    )
    dg_ref[0, 0, pl.ds(c, 1), :] = d_all + jnp.sum(
        jnp.where(i >= j, spanned + jnp.broadcast_to(after, (n, n)), 0.0)
        + jnp.where(i < j, jnp.broadcast_to(before, (n, n)), 0.0),
        axis=0, keepdims=True,
    )
    db_ref[0, 0, pl.ds(c, 1), :] = _row(d_beta, i, j)


def _params(interpret):
    import jax.experimental.pallas.tpu as pltpu

    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )


def _specs(q, v, chunk, reverse):
    """``(seq_k, seq_v, gate, state)`` block specs over the grid ``(row,
    head, step)``; ``reverse`` walks the chunks from the last."""
    import jax.experimental.pallas as pl

    _, _, t, dk = q.shape
    dv, n = v.shape[-1], t // chunk

    def at(c):
        return n - 1 - c if reverse else c

    return (
        pl.BlockSpec((1, 1, chunk, dk), lambda b, h, c: (b, h, at(c), 0)),
        pl.BlockSpec((1, 1, chunk, dv), lambda b, h, c: (b, h, at(c), 0)),
        pl.BlockSpec((1, 1, n, chunk), lambda b, h, c: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, dk, dv), lambda b, h, c: (b, h, at(c), 0, 0)),
    )


def forward(q, k, v, g, beta, *, chunk, interpret):
    """``q``, ``k`` ``(Bsz, H, T, d_k)``, ``v (Bsz, H, T, d_v)``, ``g``,
    ``beta`` ``(Bsz, H, T)`` float32, ``T`` a multiple of ``chunk`` ->
    ``(o (Bsz, H, T, d_v) in v's dtype, starts (Bsz, H, T/chunk, d_k, d_v)
    float32)``."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, heads, t, dk = q.shape
    dv, n = v.shape[-1], t // chunk
    seq_k, seq_v, gate, state = _specs(q, v, chunk, reverse=False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, heads, n),
        in_specs=[seq_k, seq_k, seq_v, gate, gate],
        out_specs=[seq_v, state],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((bsz, heads, n, dk, dv), _F32),
        ],
        name="delta_rule_fwd",
        **_params(interpret),
    )(q, k, v, g.reshape(bsz, heads, n, chunk), beta.reshape(bsz, heads, n, chunk))


def backward(q, k, v, g, beta, starts, do, *, chunk, interpret):
    """``(dq, dk, dv, dg, dbeta)``, each in its argument's shape and
    dtype."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, heads, t, dk = q.shape
    dv, n = v.shape[-1], t // chunk
    seq_k, seq_v, gate, state = _specs(q, v, chunk, reverse=True)
    rows = (bsz, heads, n, chunk)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, last_chunk=n - 1),
        grid=(bsz, heads, n),
        in_specs=[seq_k, seq_k, seq_v, gate, gate, state, seq_v],
        out_specs=[seq_k, seq_k, seq_v, gate, gate],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(rows, _F32),
            jax.ShapeDtypeStruct(rows, _F32),
        ],
        name="delta_rule_bwd",
        **_params(interpret),
    )(q, k, v, g.reshape(rows), beta.reshape(rows), starts, do)
    return dq, dk_, dv_, dg.reshape(g.shape), db.reshape(beta.shape)
