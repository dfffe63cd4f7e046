"""Lightning attention — Pallas TPU kernels ``lightning_fwd`` and
``lightning_bwd``.

The recurrence and its arguments are
:mod:`torchdistx_tpu.ops.lightning_attention`'s.  Both kernels read q, k, v
and write their results where the model keeps them, ``(Bsz, T, H*D)``, a
head's ``(chunk, D)`` block at lane block ``h``: no transpose at the doors.
The grid is ``(row, head, time chunk)``, the chunks sequential; a head's
``(D, D)`` float32 state stays in VMEM scratch from one chunk to the next
(Lightning Attention-2's tiling: the quadratic form inside a chunk under the
mask ``lambda^(i-j)``, the state across chunks).

* ``lightning_fwd`` writes each chunk's START state (the backward's
  residual, ``(Bsz, H, n_chunks, D, D)`` float32) and ``o``.
* ``lightning_bwd`` walks the chunks in REVERSE (its index maps turn the
  grid's chunk index around): ``dq`` of a chunk from its stored start
  state, ``dk`` and ``dv`` from the reverse state ``R = sum_{t >= c0}
  lambda^(t-c0) q_t^T do_t``, carried in scratch.

Every decay power is ``exp(a * n)`` with ``a = log(lambda) <= 0`` and ``n >=
0``: a fast head's powers underflow to 0, none overflows.  The quadratic
form's products take the operands' dtype (bfloat16 in training) with
float32 accumulation; the products with a state take float32 operands at
``HIGHEST`` precision, so the state is never rounded below float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["forward", "backward"]

_VMEM_LIMIT = 64 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, contract, precision=None):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32,
        precision=precision,
    )


def _decays(a, chunk):
    """``(mask, pos)``: ``mask[i, j] = exp(a (i - j))`` for ``i >= j`` else 0,
    and the positions ``(chunk, 1)`` float32."""
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = i >= j
    mask = jnp.where(
        lower, jnp.exp(a * jnp.maximum(i - j, 0).astype(jnp.float32)), 0.0
    )
    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(jnp.float32)
    return mask, pos


def _fwd_kernel(a_ref, q_ref, k_ref, v_ref, o_ref, s_out_ref, s_scr, *,
                chunk, scale):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    a = jnp.full((1, 1), a_ref[pl.program_id(1)], jnp.float32)
    mask, pos = _decays(a, chunk)
    s_prev = s_scr[...]
    s_out_ref[0, 0, 0] = s_prev
    k, v = k_ref[0], v_ref[0]
    qs = q_ref[0].astype(jnp.float32) * scale
    att = _dot(qs.astype(k.dtype), k, ((1,), (1,))) * mask
    o = _dot(att.astype(v.dtype), v, ((1,), (0,)))
    o = o + _dot(qs * jnp.exp(a * (pos + 1.0)), s_prev, ((1,), (0,)), _HI)
    o_ref[0] = o.astype(o_ref.dtype)
    kd = k.astype(jnp.float32) * jnp.exp(a * (chunk - 1.0 - pos))
    s_scr[...] = s_prev * jnp.exp(a * float(chunk)) + _dot(
        kd, v.astype(jnp.float32), ((0,), (0,)), _HI
    )


def _bwd_kernel(a_ref, q_ref, k_ref, v_ref, s_ref, do_ref, dq_ref, dk_ref,
                dv_ref, r_scr, *, chunk, scale):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)  # the row's LAST chunk
    def _():
        r_scr[...] = jnp.zeros(r_scr.shape, r_scr.dtype)

    a = jnp.full((1, 1), a_ref[pl.program_id(1)], jnp.float32)
    mask, pos = _decays(a, chunk)
    k, v, do = k_ref[0], v_ref[0], do_ref[0]
    qs = q_ref[0].astype(jnp.float32) * scale
    dof = do.astype(jnp.float32)
    r = r_scr[...]  # R of the chunks after this one
    # dq: the quadratic form, then the start state's part.
    p = _dot(do, v, ((1,), (1,))) * mask  # [i, j] = lambda^(i-j) do_i . v_j
    dqs = _dot(p.astype(k.dtype), k, ((1,), (0,)))
    dqs = dqs + _dot(
        dof * jnp.exp(a * (pos + 1.0)), s_ref[0, 0, 0], ((1,), (1,)), _HI
    )
    dq_ref[0] = (dqs * scale).astype(dq_ref.dtype)
    # dk, dv: the quadratic form, then the part of the chunks after.
    after = jnp.exp(a * (chunk - pos))
    dk = _dot(p.astype(k.dtype), qs.astype(k.dtype), ((0,), (0,)))
    dk = dk + after * _dot(v.astype(jnp.float32), r, ((1,), (1,)), _HI)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    att = _dot(qs.astype(k.dtype), k, ((1,), (1,))) * mask
    dv = _dot(att.astype(do.dtype), do, ((0,), (0,)))
    dv = dv + after * _dot(k.astype(jnp.float32), r, ((1,), (0,)), _HI)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    r_scr[...] = r * jnp.exp(a * float(chunk)) + _dot(
        qs * jnp.exp(a * pos), dof, ((0,), (0,)), _HI
    )


def _params(interpret):
    import jax.experimental.pallas.tpu as pltpu

    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )


def forward(q, k, v, log_decay, *, heads, chunk, scale, interpret):
    """``q``, ``k``, ``v`` ``(Bsz, T, H*D)``, ``T`` a multiple of ``chunk``;
    ``log_decay (H,)`` float32 -> ``(o (Bsz, T, H*D) in q's dtype, starts
    (Bsz, H, T/chunk, D, D) float32)``."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, t, width = q.shape
    d, nc = width // heads, t // chunk
    seq = pl.BlockSpec((1, chunk, d), lambda b, h, c, a: (b, c, h))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, heads, nc),
            in_specs=[seq, seq, seq],
            out_specs=[
                seq,
                pl.BlockSpec(
                    (1, 1, 1, d, d), lambda b, h, c, a: (b, h, c, 0, 0)
                ),
            ],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bsz, heads, nc, d, d), jnp.float32),
        ],
        name="lightning_fwd",
        **_params(interpret),
    )(log_decay, q, k, v)


def backward(q, k, v, log_decay, starts, do, *, heads, chunk, scale,
             interpret):
    """``(dq, dk, dv)``, each ``(Bsz, T, H*D)`` in its argument's dtype."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, t, width = q.shape
    d, nc = width // heads, t // chunk
    last = nc - 1
    seq = pl.BlockSpec((1, chunk, d), lambda b, h, c, a: (b, last - c, h))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, heads, nc),
            in_specs=[
                seq, seq, seq,
                pl.BlockSpec(
                    (1, 1, 1, d, d), lambda b, h, c, a: (b, h, last - c, 0, 0)
                ),
                seq,
            ],
            out_specs=[seq, seq, seq],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        name="lightning_bwd",
        **_params(interpret),
    )(log_decay, q, k, v, starts, do)
