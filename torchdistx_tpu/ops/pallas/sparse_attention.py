"""Block-sparse attention — Pallas TPU kernels ``sparse_flash_fwd``,
``sparse_flash_bwd_dq`` and ``sparse_flash_bwd_dkv``.

The rule and its arguments are :mod:`torchdistx_tpu.ops.sparse_attention`'s:
each token and key/value group keeps its own set of key blocks, and the
kernels compute attention over exactly those keys (and ``j <= t``).  They
read and write where the model keeps its activations, ``(Bsz, T, H*D)``:

* A q TILE is ``n`` consecutive tokens x the ``G`` query heads of one group,
  the ``(n, G*D)`` block at lane block ``g``; its heads run one after
  another inside the kernel, each an ``(n, D)`` slice on whole lane tiles.
  A kv block is ``(bk, D)`` of the group's key/value head, ``bk`` one or two
  of the rule's blocks.
* The forward and ``dq`` kernels walk, for a q tile, only the kv blocks
  that SOME token of the tile chose (their union, ascending), through the
  scalar-prefetched list ``q_lists`` and its length ``q_counts``; the grid's
  last axis has a step for every kv block, and steps past the list's length
  repeat its last block (no fetch) and compute nothing.  ``dkv`` walks, for
  a kv block, the q tiles whose union holds it (``k_lists``, ``k_counts``).
* Inside a step every token keeps exactly its own blocks: its chosen bits,
  ``(n, W)`` int32 words a token (bit ``b % 32`` of word ``b // 32`` says
  block ``b`` is chosen), and ``key <= token``.

The forward runs its step KEY-MAJOR: logits ``s^T = k q_h^T`` ``(bk, n)``,
so a token's running max and sum are ``(1, n)`` float32 rows reduced down
the sublanes (a ``(n, bk)`` step at ``bk`` 128 pays a 128-lane reduction
for every logit vreg), the accumulator ``(G, D, n)`` takes ``v^T p^T``,
and ``lse`` leaves as ``(Bsz, Hkv, G, T)`` rows.  ``dq`` reads ``lse``
per (token, head) as ``(Bsz, H, T, 1)`` columns, once a q tile.  ``dkv``,
which fetches a q tile's operands again at every kv block, runs its step
key-major too: ``p^T``, ``ds^T`` ``(bk, n)``, with ``lse`` and ``delta``
``(G, n)`` rows.  The key-major kernels take the chosen bits as ``(W, n)``
words, so no float32 operand or result of theirs is a column padded to
128 lanes.
Matrix products take the operands' dtype with float32 accumulation; the
softmax runs in float32 in the log2 domain, as ``flash_attention``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["forward", "backward_dq", "backward_dkv"]

_MASK = -1e30
_LOG2E = 1.4426950408889634
_VMEM_LIMIT = 64 * 1024 * 1024


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32
    )


def _keep(words, tile, kb, *, n, bk, bs, key_axis=1):
    """Token ``tile*n + t`` sees key ``kb*bk + j``, as a bool ``(n, bk)``
    from words ``(n, W)`` (``key_axis`` 1), or key-major ``(bk, n)`` from
    words ``(W, n)`` (``key_axis`` 0)."""
    shape = (n, bk) if key_axis == 1 else (bk, n)
    tok = tile * n + _iota(shape, 1 - key_axis)
    key = kb * bk + _iota(shape, key_axis)
    slot = _iota(words.shape, key_axis)
    part = _iota(shape, key_axis) // bs
    chosen = jnp.zeros(shape, jnp.bool_)
    for r in range(bk // bs):
        blk = kb * (bk // bs) + r
        word = jnp.sum(
            jnp.where(slot == blk // 32, words, 0), axis=key_axis, keepdims=True
        )
        bit = jax.lax.shift_right_logical(
            word, jnp.full(word.shape, blk % 32, jnp.int32)
        ) & 1
        chosen = chosen | ((part == r) & (bit != 0))
    return chosen & (key <= tok)


def _fwd_kernel(lists, counts, q_ref, k_ref, v_ref, w_ref, o_ref, lse_ref,
                acc, m_scr, l_scr, *, heads, d, n, bk, bs, scale, hkv, nq):
    import jax.experimental.pallas as pl

    b, g, i, s = (pl.program_id(x) for x in range(4))
    flat = (b * hkv + g) * nq + i
    nk = pl.num_programs(3)

    @pl.when(s == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)
        m_scr[...] = jnp.full(m_scr.shape, _MASK, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)

    @pl.when(s < counts[flat])
    def _():
        kb = lists[flat * nk + s]
        keep = _keep(w_ref[0, 0], i, kb, n=n, bk=bk, bs=bs, key_axis=0)
        k = k_ref[0]
        vt = v_ref[0].T  # (d, bk): the group's, shared by its heads
        for h in range(heads):
            # Key-major: s^T (bk, n), so a token's running max and sum are
            # reductions down the sublanes into its (1, n) rows.  A row's
            # first step keeps a key: kv block 0 holds every token's init
            # block and comes first in every list.
            st = _dot(k, q_ref[0, :, h * d:(h + 1) * d], ((1,), (1,)))
            st = jnp.where(keep, st * (scale * _LOG2E), _MASK)
            m_prev = m_scr[h]
            m_next = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp2(m_prev - m_next)
            p = jnp.exp2(st - m_next)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=0, keepdims=True)
            m_scr[h] = m_next
            acc[h] = acc[h] * alpha + _dot(vt, p.astype(vt.dtype), ((1,), (0,)))

    @pl.when(s == nk - 1)
    def _():
        for h in range(heads):
            l = l_scr[h]
            l = jnp.where(l == 0.0, 1.0, l)  # a row that kept no key
            o_ref[0, :, h * d:(h + 1) * d] = (acc[h] / l).T.astype(o_ref.dtype)
            lse_ref[0, 0, h:h + 1] = m_scr[h] / _LOG2E + jnp.log(l)


def _p_ds(q, k, v, do, lse, delta, keep, scale):
    """One head of one (q tile, kv block) pair: ``p`` and ``ds``."""
    s = _dot(q, k, ((1,), (1,))) * scale
    p = jnp.where(keep, jnp.exp(s - lse), 0.0)
    dp = _dot(do, v, ((1,), (1,)))
    return p, p * (dp - delta)


def _dq_kernel(lists, counts, q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_acc, *, heads, d, n, bk, bs, scale, hkv,
               nq):
    import jax.experimental.pallas as pl

    b, g, i, s = (pl.program_id(x) for x in range(4))
    flat = (b * hkv + g) * nq + i
    nk = pl.num_programs(3)

    @pl.when(s == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    @pl.when(s < counts[flat])
    def _():
        kb = lists[flat * nk + s]
        keep = _keep(w_ref[0, 0], i, kb, n=n, bk=bk, bs=bs)
        k, v = k_ref[0], v_ref[0]
        for h in range(heads):
            cols = slice(h * d, (h + 1) * d)
            _, ds = _p_ds(
                q_ref[0, :, cols], k, v, do_ref[0, :, cols], lse_ref[0, h],
                delta_ref[0, h], keep, scale,
            )
            dq_acc[:, cols] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(s == nk - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(lists, counts, q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, heads, d, n, bk,
                bs, scale, hkv, nk):
    import jax.experimental.pallas as pl

    b, g, kb, s = (pl.program_id(x) for x in range(4))
    flat = (b * hkv + g) * nk + kb
    nq = pl.num_programs(3)

    @pl.when(s == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)

    @pl.when(s < counts[flat])
    def _():
        i = lists[flat * nq + s]
        keep = _keep(w_ref[0, 0], i, kb, n=n, bk=bk, bs=bs, key_axis=0)
        k, v = k_ref[0], v_ref[0]
        for h in range(heads):
            cols = slice(h * d, (h + 1) * d)
            q, do = q_ref[0, :, cols], do_ref[0, :, cols]
            # Key-major: _p_ds with the roles swapped gives p^T and ds^T
            # (bk, n), the statistics one (1, n) row each, so both products
            # below contract the tile's n tokens as a plain matmul does.
            p, ds = _p_ds(
                k, q, do, v, lse_ref[0, 0, h:h + 1], delta_ref[0, 0, h:h + 1],
                keep, scale,
            )
            dv_acc[...] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
            dk_acc[...] += _dot(ds.astype(q.dtype), q, ((1,), (0,)))

    @pl.when(s == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _params(interpret):
    import jax.experimental.pallas.tpu as pltpu

    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )


def _shapes(q, k, words, n, bk):
    bsz, t, width = q.shape
    hkv, w = words.shape[1], words.shape[-1]
    d = k.shape[-1] // hkv
    return bsz, t, hkv, width // (hkv * d), d, t // n, t // bk, w


def forward(q, k, v, words, q_lists, q_counts, *, n, bk, bs, interpret):
    """``q (Bsz, T, H*D)``, ``k``/``v (Bsz, T, Hkv*D)``, ``words (Bsz, Hkv,
    T, W)`` int32, the tiles' lists flat -> ``(o (Bsz, T, H*D), lse (Bsz,
    Hkv, G, T) float32)``.  The step is key-major: a token's running max
    and sum are ``(1, n)`` rows, its chosen bits ``(W, n)`` words, the
    accumulator ``(G, D, n)``, and ``lse`` leaves as ``(G, n)`` rows."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, t, hkv, g, d, nq, nk, w = _shapes(q, k, words, n, bk)

    def kv_index(b, gi, i, s, lists, counts):
        return (b, lists[((b * hkv + gi) * nq + i) * nk + s], gi)

    def lanes(b, gi, i, s, *_):
        return (b, gi, 0, i)

    tile = pl.BlockSpec((1, n, g * d), lambda b, gi, i, s, *_: (b, i, gi))
    kv = pl.BlockSpec((1, bk, d), kv_index)
    kern = functools.partial(
        _fwd_kernel, heads=g, d=d, n=n, bk=bk, bs=bs, scale=d**-0.5, hkv=hkv,
        nq=nq,
    )
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, hkv, nq, nk),
            in_specs=[tile, kv, kv, pl.BlockSpec((1, 1, w, n), lanes)],
            out_specs=[tile, pl.BlockSpec((1, 1, g, n), lanes)],
            scratch_shapes=[
                pltpu.VMEM((g, d, n), jnp.float32),
                pltpu.VMEM((g, 1, n), jnp.float32),
                pltpu.VMEM((g, 1, n), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bsz, hkv, g, t), jnp.float32),
        ],
        name="sparse_flash_fwd",
        **_params(interpret),
    )(q_lists, q_counts, q, k, v, jnp.swapaxes(words, 2, 3))


def backward_dq(q, k, v, words, do, lse, delta, q_lists, q_counts, *, n, bk,
                bs, interpret):
    """``dq (Bsz, T, H*D)``; ``lse``, ``delta`` ``(Bsz, H, T, 1)`` float32."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, t, hkv, g, d, nq, nk, w = _shapes(q, k, words, n, bk)

    def kv_index(b, gi, i, s, lists, counts):
        return (b, lists[((b * hkv + gi) * nq + i) * nk + s], gi)

    tile = pl.BlockSpec((1, n, g * d), lambda b, gi, i, s, *_: (b, i, gi))
    rows = pl.BlockSpec((1, g, n, 1), lambda b, gi, i, s, *_: (b, gi, i, 0))
    kv = pl.BlockSpec((1, bk, d), kv_index)
    kern = functools.partial(
        _dq_kernel, heads=g, d=d, n=n, bk=bk, bs=bs, scale=d**-0.5, hkv=hkv,
        nq=nq,
    )
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, hkv, nq, nk),
            in_specs=[
                tile, kv, kv,
                pl.BlockSpec((1, 1, n, w), lambda b, gi, i, s, *_: (b, gi, i, 0)),
                tile, rows, rows,
            ],
            out_specs=tile,
            scratch_shapes=[pltpu.VMEM((n, g * d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="sparse_flash_bwd_dq",
        **_params(interpret),
    )(q_lists, q_counts, q, k, v, words, do, lse, delta)


def backward_dkv(q, k, v, words, do, lse, delta, k_lists, k_counts, *, n, bk,
                 bs, interpret):
    """``(dk, dv)``, each ``(Bsz, T, Hkv*D)``; ``lse``, ``delta`` ``(Bsz, H,
    T)`` float32.  The step is key-major: a q tile's statistics enter as
    ``(G, n)`` rows and its chosen bits as ``(W, n)`` words, where columns
    would be tiled to 128 lanes and fetched again at every kv block."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bsz, t, hkv, g, d, nq, nk, w = _shapes(q, k, words, n, bk)

    def q_at(b, gi, kb, s, lists):
        return lists[((b * hkv + gi) * nk + kb) * nq + s]

    def lanes(b, gi, kb, s, lists, counts):
        return (b, gi, 0, q_at(b, gi, kb, s, lists))

    tile = pl.BlockSpec(
        (1, n, g * d),
        lambda b, gi, kb, s, lists, counts: (b, q_at(b, gi, kb, s, lists), gi),
    )
    rows = pl.BlockSpec((1, 1, g, n), lanes)
    kv = pl.BlockSpec((1, bk, d), lambda b, gi, kb, s, *_: (b, kb, gi))
    kern = functools.partial(
        _dkv_kernel, heads=g, d=d, n=n, bk=bk, bs=bs, scale=d**-0.5, hkv=hkv,
        nk=nk,
    )
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, hkv, nk, nq),
            in_specs=[
                tile, kv, kv, pl.BlockSpec((1, 1, w, n), lanes), tile, rows, rows,
            ],
            out_specs=[kv, kv],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        name="sparse_flash_bwd_dkv",
        **_params(interpret),
    )(
        k_lists, k_counts, q, k, v, jnp.swapaxes(words, 2, 3), do,
        lse.reshape(bsz, hkv, g, t), delta.reshape(bsz, hkv, g, t),
    )
