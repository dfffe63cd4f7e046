"""Selective scan — Pallas TPU kernels ``ssm_scan_fwd`` and ``ssm_scan_bwd``.

The recurrence and its arguments are :mod:`torchdistx_tpu.ops.selective_scan`'s.
Both kernels run a grid ``(row, time chunk, channel block)``, the time
chunks sequential and the channel blocks innermost.  A channel block's
state is an ``(N, bc)`` float32 tile, the ``N`` states on sublanes and
``bc`` channels on lanes, so that ``delta_t`` and ``u_t`` (one row of a
``(chunk, bc)`` block) broadcast along sublanes and ``B_t`` / ``C_t`` (one
``(N, 1)`` column) along lanes, and each of the chunk's positions is a
handful of element-wise operations on ``N * bc / 1024`` vector registers.
The states of ALL channel blocks stay in VMEM scratch from one time chunk
to the next (``N * C`` floats: 320 KiB at 16 x 5,120), which is what lets
the channel blocks run innermost: a time chunk's ``B`` and ``C`` are then
fetched once for all its channel blocks, and ``dB`` / ``dC``, sums over
ALL channels, accumulate in one resident output block (with the time
chunks innermost each channel block wrote its own partial sums: 1.3 GB a
layer at the cell's shapes).  ``B`` and ``C`` enter as ``(Bsz, T, N, 1)``
float32 (a column a position; 128 lanes of padding in VMEM).

* ``ssm_scan_fwd`` writes the state at each chunk's start (the backward's
  residual) and ``y`` with the ``D * u`` skip.
* ``ssm_scan_bwd`` walks the time chunks in REVERSE (its index maps turn
  the grid's chunk index around), recomputes the chunk's states from its
  start state into VMEM, then runs the positions backwards carrying ``dh``
  in scratch across chunks.  ``dA`` and ``dD`` accumulate in output blocks
  that stay resident for the whole row; the sums over a block's channels
  that ``dB`` and ``dC`` need are folded to 128 lanes on the vector unit
  position by position and reduced across lanes once a chunk.

Blocks of 1,024 channels and 16 (forward) or 8 (backward) positions a
trip of the in-kernel loops, from a sweep on a v5e at 4,096 x 5,120 x 16
(PERF.md section 6, PR 31: blocks of 512 at 8 and 4 took 1.28 x the time).
VMEM at chunks of 128 and those blocks: the backward's recomputed states
8 MiB, the padded ``B``/``C``/``dB``/``dC`` blocks 1 MiB each and
double-buffered, the folded sums 2 MiB; the limit is stated (Mosaic's
default is 16 MiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["forward", "backward"]

_VMEM_LIMIT = 64 * 1024 * 1024
# Channels a block holds: the widest of these that divides the channels
# (the whole of them when none does, which only tiny test sizes meet).
_BLOCKS = (1024, 512, 256, 128)
# Positions a trip of the in-kernel loops runs (unrolled by hand).
_UNROLL_FWD = 16
_UNROLL_BWD = 8


def _block(channels: int) -> int:
    return next((b for b in _BLOCKS if channels % b == 0), channels)


def _fold(x, width):
    """``(N, bc)`` -> ``(N, width)``: the sum of the ``width``-lane columns
    (whole vector registers: no cross-lane work)."""
    out = x[:, :width]
    for lo in range(width, x.shape[1], width):
        out = out + x[:, lo:lo + width]
    return out


def _loop(n, body, init, unroll):
    """``fori_loop(0, n, body, init)`` with ``unroll`` steps a trip, unrolled
    by hand (Mosaic's own ``unroll`` is all or nothing)."""
    if n % unroll:
        unroll = 1

    def trip(i, carry):
        for j in range(unroll):
            carry = body(i * unroll + j, carry)
        return carry

    return jax.lax.fori_loop(0, n // unroll, trip, init)


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hs_ref,
                h_scr, dt_scr, x_scr, y_scr, *, chunk):
    import jax.experimental.pallas as pl

    ci = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[ci] = jnp.zeros(h_scr.shape[1:], h_scr.dtype)

    hs_ref[0, 0] = h_scr[ci]
    u = u_ref[0].astype(jnp.float32)
    dt = dt_ref[0].astype(jnp.float32)
    dt_scr[...] = dt
    x_scr[...] = dt * u
    a = a_ref[...]

    def step(t, h):
        row = pl.ds(t, 1)
        h = jnp.exp(dt_scr[row, :] * a) * h + x_scr[row, :] * b_ref[0, t]
        y_scr[row, :] = jnp.sum(h * c_ref[0, t], axis=0, keepdims=True)
        return h

    h_scr[ci] = _loop(chunk, step, h_scr[ci], _UNROLL_FWD)
    y_ref[0] = (y_scr[...] + d_ref[...] * u).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, hs_ref, dy_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
                dh_scr, hprev_scr, dt_scr, x_scr, dy_scr, s1_scr, s2_scr,
                dbf_scr, dcf_scr, *, chunk, width):
    import jax.experimental.pallas as pl

    ci = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)  # the row's LAST chunk
    def _():
        dh_scr[ci] = jnp.zeros(dh_scr.shape[1:], dh_scr.dtype)
        da_ref[0, ci] = jnp.zeros(da_ref.shape[2:], da_ref.dtype)
        dd_ref[0, ci] = jnp.zeros(dd_ref.shape[2:], dd_ref.dtype)

    u = u_ref[0].astype(jnp.float32)
    dt = dt_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    dt_scr[...] = dt
    x_scr[...] = dt * u
    dy_scr[...] = dy
    a = a_ref[...]

    # The chunk's states again, each position's PREVIOUS state kept.
    def again(t, h):
        row = pl.ds(t, 1)
        hprev_scr[t] = h
        return jnp.exp(dt_scr[row, :] * a) * h + x_scr[row, :] * b_ref[0, t]

    h_end = _loop(chunk, again, hs_ref[0, 0], _UNROLL_FWD)

    def back(i, carry):
        dh, h, da = carry
        t = chunk - 1 - i
        row = pl.ds(t, 1)
        dy_t, dt_t, x_t = dy_scr[row, :], dt_scr[row, :], x_scr[row, :]
        dcf_scr[t] = _fold(h * dy_t, width)
        dh = dh + c_ref[0, t] * dy_t
        dbf_scr[t] = _fold(dh * x_t, width)
        s1_scr[row, :] = jnp.sum(dh * b_ref[0, t], axis=0, keepdims=True)
        h_prev = hprev_scr[t]
        decay = jnp.exp(dt_t * a)
        dh = dh * decay  # d h_{t-1}, and d(dt_t * A) = dh * h_{t-1}
        g = dh * h_prev
        s2_scr[row, :] = jnp.sum(g * a, axis=0, keepdims=True)
        return dh, h_prev, da + g * dt_t

    dh, _, da = _loop(
        chunk, back, (dh_scr[ci], h_end, jnp.zeros_like(a)), _UNROLL_BWD
    )
    dh_scr[ci] = dh
    da_ref[0, ci] += da
    dd_ref[0, ci] += jnp.sum(dy * u, axis=0, keepdims=True)
    s1 = s1_scr[...]
    du_ref[0] = (dt * s1 + d_ref[...] * dy).astype(du_ref.dtype)
    ddt_ref[0] = (s2_scr[...] + u * s1).astype(ddt_ref.dtype)
    # Over the channel blocks of this time chunk, in the resident block.
    db = jnp.sum(dbf_scr[...], axis=-1, keepdims=True)
    dc = jnp.sum(dcf_scr[...], axis=-1, keepdims=True)

    @pl.when(ci == 0)
    def _():
        db_ref[0] = db
        dc_ref[0] = dc

    @pl.when(ci != 0)
    def _():
        db_ref[0] += db
        dc_ref[0] += dc


def _columns(x):
    """``(Bsz, T, N)`` -> ``(Bsz, T, N, 1)`` float32."""
    return x.astype(jnp.float32)[..., None]


def _doors(a, b, c, d):
    """``A``, ``B``, ``C``, ``D`` as the kernels take them, under the scope
    ``relayout`` (with ``backward``'s results on their way out: what the
    kernels' doors cost beside the kernels)."""
    with jax.named_scope("relayout"):
        return a.T, _columns(b), _columns(c), d.astype(jnp.float32)[None]


def _params(interpret):
    import jax.experimental.pallas.tpu as pltpu

    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )


def forward(u, dt, a, b, c, d, *, chunk, interpret):
    """``(y, starts)``: ``y (Bsz, T, C)`` in ``u``'s dtype with the skip,
    ``starts (Bsz, T/chunk, N, C)`` float32."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, t, ch = u.shape
    n = a.shape[1]
    bc, nt = _block(ch), t // chunk
    nb = ch // bc
    seq = pl.BlockSpec((1, chunk, bc), lambda bi, ti, ci: (bi, ti, ci))
    col = pl.BlockSpec((1, chunk, n, 1), lambda bi, ti, ci: (bi, ti, 0, 0))
    per_channel = pl.BlockSpec((n, bc), lambda bi, ti, ci: (0, ci))
    skip = pl.BlockSpec((1, bc), lambda bi, ti, ci: (0, ci))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(bsz, nt, nb),
        in_specs=[seq, seq, per_channel, col, col, skip],
        out_specs=[
            seq,
            pl.BlockSpec((1, 1, n, bc), lambda bi, ti, ci: (bi, ti, 0, ci)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((bsz, nt, n, ch), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nb, n, bc), jnp.float32)]
        + [pltpu.VMEM((chunk, bc), jnp.float32)] * 3,
        name="ssm_scan_fwd",
        **_params(interpret),
    )(u, dt, *_doors(a, b, c, d))


def backward(u, dt, a, b, c, d, starts, dy, *, chunk, interpret):
    """``(du, ddt, dA, dB, dC, dD)`` in float32 but ``du`` and ``ddt``
    (``u``'s dtype): ``dA (C, N)``, ``dB``/``dC (Bsz, T, N)``, ``dD (C,)``."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, t, ch = u.shape
    n = a.shape[1]
    bc, nt = _block(ch), t // chunk
    nb = ch // bc
    width = 128 if bc % 128 == 0 else bc
    last = nt - 1
    seq = pl.BlockSpec((1, chunk, bc), lambda bi, ti, ci: (bi, last - ti, ci))
    col = pl.BlockSpec((1, chunk, n, 1), lambda bi, ti, ci: (bi, last - ti, 0, 0))
    per_channel = pl.BlockSpec((n, bc), lambda bi, ti, ci: (0, ci))
    skip = pl.BlockSpec((1, bc), lambda bi, ti, ci: (0, ci))

    def whole_row(rows):  # resident over a row's whole grid
        return pl.BlockSpec((1, nb, rows, bc), lambda bi, ti, ci: (bi, 0, 0, 0))

    du, ddt, da, db, dc, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, width=width),
        grid=(bsz, nt, nb),
        in_specs=[
            seq, seq, per_channel, col, col, skip,
            pl.BlockSpec((1, 1, n, bc), lambda bi, ti, ci: (bi, last - ti, 0, ci)),
            seq,
        ],
        out_specs=[seq, seq, whole_row(n), col, col, whole_row(1)],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((bsz, nb, n, bc), jnp.float32),
            jax.ShapeDtypeStruct((bsz, t, n, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz, t, n, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nb, 1, bc), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, n, bc), jnp.float32),
            pltpu.VMEM((chunk, n, bc), jnp.float32),
        ]
        + [pltpu.VMEM((chunk, bc), jnp.float32)] * 5
        + [pltpu.VMEM((chunk, n, width), jnp.float32)] * 2,
        name="ssm_scan_bwd",
        **_params(interpret),
    )(u, dt, *_doors(a, b, c, d), starts, dy)
    with jax.named_scope("relayout"):
        return (
            du, ddt, da.sum(0).transpose(1, 0, 2).reshape(n, ch).T,
            db[..., 0], dc[..., 0], dd.sum(0).reshape(ch),
        )
