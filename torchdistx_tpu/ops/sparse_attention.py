"""Block-sparse attention that picks its own blocks per token: InfLLM-v2
(arXiv:2509.24663; the MiniCPM4 report, arXiv:2506.07900), the ``minicpm4``
mixer of MiniCPM-SALA.

Keys are cut into blocks of ``block_size``.  For each token ``t`` and each
key/value GROUP (the query heads that share one key/value head) the rule
keeps ``topk`` blocks, and the token attends to the keys ``j <= t`` inside
them::

    Kc_j     = mean(k[stride*j : stride*j + kernel])      # compressed keys
    visible  : stride*j + kernel <= t + 1
    sc[t, j] = sum_{h in group} softmax_j(q_{t,h} . Kc_j / sqrt(D))
    s[t, b]  = max of sc[t, j] over the windows j that overlap block b
    chosen   = block 0 .. init_blocks - 1, the blocks that overlap the local
               window [t - window + 1, t], then the best-scoring of the other
               blocks j <= t, up to topk blocks in all
    o_{t,h}  = softmax over the kept keys of (q_{t,h} . k_j / sqrt(D)) . v_j

The choice has no gradient (it reads ``stop_gradient`` of q and k); the
gradient flows through the attention over the chosen keys.  At ``T <=
dense_len`` the layer is plain causal attention
(:func:`torchdistx_tpu.ops.attention.attention`, the flash kernels).

``sparse_attention(q, k, v, cfg)``: ``q (Bsz, T, H, D)``, ``k``/``v (Bsz, T,
Hkv, D)`` -> ``(o (Bsz, T, H, D), stats)``; query head ``n`` reads
key/value head ``n // (H / Hkv)``.  ``T`` a multiple of ``block_size``.

* ``select_blocks`` makes the choice in ``jax.numpy``, query rows in blocks
  of ``ROWS`` so that the ``(T, G, T/stride)`` scores never exist whole, and
  returns each (token, group)'s set as bits, ``(Bsz, Hkv, T, W)`` int32
  words.  Under the scope ``select``.
* The attention over the chosen keys is ONE ``jax.custom_vjp`` for both
  implementations, under the scope ``sparse``: ``impl="jnp"``, a masked
  attention in query-row blocks with the backward written out (the CPU
  path and the kernels' oracle), or ``impl="pallas"``, the kernels
  ``sparse_flash_fwd`` / ``sparse_flash_bwd_dq`` / ``sparse_flash_bwd_dkv``
  (:mod:`torchdistx_tpu.ops.pallas.sparse_attention`), which walk only the
  kv blocks some token of a q tile chose and mask per token.  ``auto``:
  ``pallas`` on a TPU, else ``jnp``.

The forward rule names its result ``sparse_out`` and its log-sum-exp
``sparse_lse``; the caller names the chosen bits ``sparse_choice``, and
:data:`torchdistx_tpu.ops.remat.REMAT_POLICY` keeps all three: a block's
replay must not choose again (its bfloat16 scores could flip a near tie,
and the kept result would belong to another choice than the backward's).

The forward kernel runs key-major and hands ``lse`` back as ``(Bsz, Hkv,
G, T)`` rows, which reshape to ``(Bsz, H, T)`` for free.

``stats["kv_blocks"]``: the mean over q tiles of the keys a tile's kernel
steps run, in blocks of ``block_size`` (a device scalar; a token chose
``topk``).  Counted per trace: ``sparse_attn.mode{mode=dense|sparse}``,
``sparse_attn{impl=}``, ``sparse_attn{interpret=}`` (Pallas only); per
trace of the Pallas forward, ``sparse_attn.fwd_stats{layout=rows}`` (its
running statistics and ``lse`` are rows); per trace of the Pallas
backward, ``sparse_attn.bwd_stats{layout=}`` once for each kernel by how
``lse`` and ``delta`` enter it: ``columns`` (dq, ``(Bsz, H, T, 1)``),
``rows`` (dkv, ``(Bsz, H, T)``).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry as _telemetry

__all__ = [
    "SparseConfig", "sparse_attention", "select_blocks", "unpack_blocks",
    "resolve_impl",
]

ROWS = 512  # query rows a step of the choice scores
_TILE = (256, 128, 64, 32, 16, 8)  # tokens a q tile holds: the widest that divides T


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """InfLLM-v2's ``sparse_config`` (MiniCPM4-8B's values)."""

    kernel_size: int = 32
    kernel_stride: int = 16
    init_blocks: int = 1
    block_size: int = 64
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel_size % self.kernel_stride or self.block_size % self.kernel_stride:
            raise ValueError("kernel_size and block_size must be multiples of kernel_stride")


# ---------------------------------------------------------------------------
# The choice.


def compress_keys(k, cfg: SparseConfig):
    """``k (Bsz, T, Hkv, D)`` -> ``(Bsz, NC, Hkv, D)`` float32 means of the
    windows ``[stride*j, stride*j + kernel)``."""
    b, t, hkv, d = k.shape
    st, r = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    sums = k.astype(jnp.float32).reshape(b, t // st, st, hkv, d).sum(2)
    nc = t // st - r + 1
    return sum(sums[:, u:u + nc] for u in range(r)) / cfg.kernel_size


def _block_scores(sc, cfg: SparseConfig, n_blocks):
    """``sc (..., NC)`` -> ``(..., NB)``: the max over the windows that
    overlap each block (-inf where none is visible)."""
    bp, r = cfg.block_size // cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    nc = sc.shape[-1]
    # Window j overlaps block b iff b*bp - (r - 1) <= j < (b + 1) * bp.
    pad = [(0, 0)] * (sc.ndim - 1) + [(r - 1, n_blocks * bp - nc)]
    padded = jnp.pad(sc, pad, constant_values=-jnp.inf)
    out = padded[..., r - 1:].reshape(*sc.shape[:-1], n_blocks, bp).max(-1)
    for off in range(r - 1):
        out = jnp.maximum(out, padded[..., off::bp][..., :n_blocks])
    return out


def choice_keys(scores, rows, cfg: SparseConfig):
    """What ``top_k`` ranks: block scores ``(..., R, NB)`` of the query
    rows at positions ``rows (R,)``, forced blocks first (+inf), blocks
    after the row's own last (-inf)."""
    nb = scores.shape[-1]
    blk = jnp.arange(nb)
    t = rows[:, None]
    causal = blk * cfg.block_size <= t
    local = (blk + 1) * cfg.block_size - 1 >= t - cfg.window_size + 1
    forced = causal & (local | (blk < cfg.init_blocks))
    return jnp.where(
        forced, jnp.inf, jnp.where(causal, jnp.maximum(scores, -1e30), -jnp.inf)
    )


def chosen_from_keys(keys, topk):
    """``(..., NB)`` bool: the ``topk`` largest keys, none at -inf; of keys
    tied at the last place the earlier blocks (two blocks share the max of
    the window that straddles them, so ties are no accident)."""
    kth = jax.lax.top_k(keys, min(topk, keys.shape[-1]))[0][..., -1:]
    tied = keys == kth
    room = topk - (keys > kth).sum(-1, keepdims=True)
    first = jnp.cumsum(tied, axis=-1) <= room
    return ((keys > kth) | (tied & first)) & (keys > -jnp.inf)


def group_scores(q, kc, rows, cfg: SparseConfig):
    """``q (Bsz, R, Hkv, G, D)`` at positions ``rows``, ``kc (Bsz, NC, Hkv,
    D)`` -> the block scores ``(Bsz, Hkv, R, NB)`` float32, NB = T / block."""
    d = q.shape[-1]
    s = jnp.einsum(
        "brhgd,bjhd->bhgrj", q, kc.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) / math.sqrt(d)
    visible = (
        jnp.arange(kc.shape[1]) * cfg.kernel_stride + cfg.kernel_size
        <= rows[:, None] + 1
    )
    s = jnp.where(visible, s, -jnp.inf)
    p = jnp.where(visible.any(-1, keepdims=True), jax.nn.softmax(s, axis=-1), 0.0)
    return p.sum(2)


def select_blocks(q, k, cfg: SparseConfig):
    """The choice (module docstring) as bits: ``(Bsz, Hkv, T, W)`` int32,
    ``W = ceil(NB / 32)``.  ``q (Bsz, T, H, D)``, ``k (Bsz, T, Hkv, D)``."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    nb = t // cfg.block_size
    q = jax.lax.stop_gradient(q).reshape(b, t, hkv, h // hkv, d)
    kc = compress_keys(jax.lax.stop_gradient(k), cfg)
    size = math.gcd(ROWS, t)

    def rows_of(lo):
        rows = lo + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, size, axis=1)
        scores = _block_scores(group_scores(qb, kc, rows, cfg), cfg, nb)
        return _pack(chosen_from_keys(choice_keys(scores, rows, cfg), cfg.topk))

    words = jax.lax.map(rows_of, jnp.arange(0, t, size))  # (n, Bsz, Hkv, R, W)
    return jnp.moveaxis(words, 0, 2).reshape(b, hkv, t, -1)


def _pack(chosen):
    """``(..., NB)`` bool -> ``(..., ceil(NB / 32))`` int32 words."""
    nb = chosen.shape[-1]
    w = -(-nb // 32)
    bits = jnp.pad(chosen, [(0, 0)] * (chosen.ndim - 1) + [(0, 32 * w - nb)])
    bits = bits.reshape(*chosen.shape[:-1], w, 32).astype(jnp.uint32)
    word = (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(word, jnp.int32)


def unpack_blocks(words, n_blocks):
    """``(..., W)`` int32 words -> ``(..., n_blocks)`` bool."""
    u = jax.lax.bitcast_convert_type(words, jnp.uint32)
    bits = (u[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(*words.shape[:-1], -1)[..., :n_blocks].astype(jnp.bool_)


# ---------------------------------------------------------------------------
# The kernels' lists: for a q tile the kv blocks its tokens chose (their
# union, ascending), for a kv block the q tiles that chose it.


def _lists(member):
    """``member (..., M)`` bool -> ``(lists (..., M) int32, counts (...,))``:
    the members' indices ascending, then the last repeated."""
    m = member.shape[-1]
    idx = jnp.arange(m, dtype=jnp.int32)
    order = jnp.sort(jnp.where(member, idx, m + idx), axis=-1)
    counts = member.sum(-1, dtype=jnp.int32)
    last = jnp.take_along_axis(order, jnp.maximum(counts - 1, 0)[..., None], -1)
    lists = jnp.where(idx < counts[..., None], order, last)
    return jnp.where(lists >= m, 0, lists), counts


def _tiling(t, block_size):
    n = next(x for x in _TILE if t % x == 0)
    bk = 2 * block_size if t % (2 * block_size) == 0 and block_size < 128 else block_size
    return n, bk


def _membership(words, t, cfg, n, bk):
    """``(Bsz, Hkv, T/n, T/bk)`` bool: some token of the q tile chose a
    block inside the kv block."""
    b, hkv = words.shape[:2]
    chosen = unpack_blocks(words, t // cfg.block_size)
    return chosen.reshape(
        b, hkv, t // n, n, t // bk, bk // cfg.block_size
    ).any((3, 5))


# ---------------------------------------------------------------------------
# impl="jnp": masked attention in query-row blocks.


def _keys_seen(words, rows, t, cfg):
    """``(Bsz, Hkv, R, T)`` bool for the query rows ``rows``."""
    chosen = unpack_blocks(words, t // cfg.block_size)
    keys = jnp.repeat(chosen, cfg.block_size, axis=-1)
    return keys & (jnp.arange(t)[None, :] <= rows[:, None])


def _jnp_forward(q, k, v, words, cfg):
    """``q (Bsz, T, Hkv, G, D)`` -> ``(o like q, lse (Bsz, Hkv, G, T))``."""
    b, t, hkv, g, d = q.shape
    size = math.gcd(ROWS, t)
    outs, lses = [], []
    for lo in range(0, t, size):
        rows = jnp.arange(lo, lo + size)
        seen = _keys_seen(words[:, :, lo:lo + size], rows, t, cfg)
        s = jnp.einsum(
            "brhgd,bjhd->bhgrj", q[:, lo:lo + size], k,
            preferred_element_type=jnp.float32,
        ) / math.sqrt(d)
        s = jnp.where(seen[:, :, None], s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        outs.append(jnp.einsum("bhgrj,bjhd->brhgd", p.astype(v.dtype), v,
                               preferred_element_type=jnp.float32))
        lses.append(lse)
    return (
        jnp.concatenate(outs, axis=1).astype(q.dtype),
        jnp.concatenate(lses, axis=-1),
    )


def _jnp_backward(q, k, v, words, lse, delta, do, cfg):
    b, t, hkv, g, d = q.shape
    size = math.gcd(ROWS, t)
    scale = 1.0 / math.sqrt(d)
    dq, dk, dv = [], jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)
    for lo in range(0, t, size):
        sl = slice(lo, lo + size)
        seen = _keys_seen(words[:, :, sl], jnp.arange(lo, lo + size), t, cfg)
        s = jnp.einsum("brhgd,bjhd->bhgrj", q[:, sl], k,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.where(seen[:, :, None], jnp.exp(s - lse[..., sl, None]), 0.0)
        dp = jnp.einsum("brhgd,bjhd->bhgrj", do[:, sl], v,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., sl, None])
        dv = dv + jnp.einsum("bhgrj,brhgd->bjhd", p, do[:, sl].astype(jnp.float32))
        dk = dk + jnp.einsum("bhgrj,brhgd->bjhd", ds, q[:, sl].astype(jnp.float32)) * scale
        dq.append(jnp.einsum("bhgrj,bjhd->brhgd", ds, k.astype(jnp.float32)) * scale)
    return jnp.concatenate(dq, axis=1), dk, dv


# ---------------------------------------------------------------------------
# The differentiable entry: q (Bsz, T, H*D), k / v (Bsz, T, Hkv*D), the
# model's own layout; ``words`` is not differentiated.


def _forward(q, k, v, words, cfg, impl, interpret):
    """``(o (Bsz, T, H*D), lse (Bsz, H, T) float32)``."""
    b, t, _ = q.shape
    hkv = words.shape[1]
    d = k.shape[-1] // hkv
    g = q.shape[-1] // (hkv * d)
    if impl == "pallas":
        from .pallas import sparse_attention as kernels

        n, bk = _tiling(t, cfg.block_size)
        lists, counts = _lists(_membership(words, t, cfg, n, bk))
        o, lse = kernels.forward(
            q, k, v, words, lists.reshape(-1), counts.reshape(-1), n=n, bk=bk,
            bs=cfg.block_size, interpret=interpret,
        )
        _telemetry.counter("sparse_attn.fwd_stats", layout="rows").add()
        return o, lse.reshape(b, hkv * g, t)
    o, lse = _jnp_forward(
        q.reshape(b, t, hkv, g, d), k.reshape(b, t, hkv, d),
        v.reshape(b, t, hkv, d), words, cfg,
    )
    return o.reshape(q.shape), lse.reshape(b, hkv * g, t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sparse(q, k, v, words, cfg, impl, interpret):
    return _forward(q, k, v, words, cfg, impl, interpret)[0]


def _sparse_fwd(q, k, v, words, cfg, impl, interpret):
    o, lse = _forward(q, k, v, words, cfg, impl, interpret)
    # The named results are the saved ones (flash_attention says why).
    o = checkpoint_name(o, "sparse_out")
    lse = checkpoint_name(lse, "sparse_lse")
    return o, (q, k, v, words, o, lse)


def _sparse_bwd(cfg, impl, interpret, res, do):
    q, k, v, words, o, lse = res
    b, t, _ = q.shape
    hkv = words.shape[1]
    d = k.shape[-1] // hkv
    h = q.shape[-1] // d
    with jax.named_scope("delta"):
        delta = jnp.sum(
            do.astype(jnp.float32).reshape(b, t, h, d)
            * o.astype(jnp.float32).reshape(b, t, h, d), axis=-1,
        ).transpose(0, 2, 1)  # (Bsz, H, T)
    if impl == "pallas":
        from .pallas import sparse_attention as kernels

        n, bk = _tiling(t, cfg.block_size)
        member = _membership(words, t, cfg, n, bk)
        q_lists, q_counts = _lists(member)
        k_lists, k_counts = _lists(jnp.swapaxes(member, 2, 3))
        kw = dict(n=n, bk=bk, bs=cfg.block_size, interpret=interpret)
        # dq reads a tile's statistics once, as (T, 1) columns; dkv at every
        # kv block, so it takes them as the (T,) rows they are.
        dq = kernels.backward_dq(
            q, k, v, words, do, lse[..., None], delta[..., None],
            q_lists.reshape(-1), q_counts.reshape(-1), **kw
        )
        dk, dv = kernels.backward_dkv(
            q, k, v, words, do, lse, delta, k_lists.reshape(-1),
            k_counts.reshape(-1), **kw
        )
        _telemetry.counter("sparse_attn.bwd_stats", layout="columns").add()
        _telemetry.counter("sparse_attn.bwd_stats", layout="rows").add()
        return dq, dk, dv, None
    g = h // hkv
    dq, dk, dv = _jnp_backward(
        q.reshape(b, t, hkv, g, d), k.reshape(b, t, hkv, d),
        v.reshape(b, t, hkv, d), words, lse.reshape(b, hkv, g, t),
        delta.reshape(b, hkv, g, t), do.reshape(b, t, hkv, g, d), cfg,
    )
    return (
        dq.reshape(q.shape).astype(q.dtype), dk.reshape(k.shape).astype(k.dtype),
        dv.reshape(v.shape).astype(v.dtype), None,
    )


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """``auto`` -> ``pallas`` on a TPU, else ``jnp``; the others checked."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown sparse_attention impl: {impl!r} (auto|jnp|pallas)")
    return impl


def sparse_attention(q, k, v, cfg: SparseConfig, *, impl: str = "auto",
                     dense_impl: str = "auto", mesh=None):
    """The layer's attention (module docstring) -> ``(o (Bsz, T, H, D),
    stats)``.  At ``T <= cfg.dense_len`` plain causal attention through
    :func:`~torchdistx_tpu.ops.attention.attention` with ``dense_impl``
    and ``mesh`` (``stats`` empty); else the sparse path (the kernels on
    one device; ``jnp`` partitions anywhere)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if t <= cfg.dense_len:
        from .attention import attention

        _telemetry.counter("sparse_attn.mode", mode="dense").add()
        return attention(q, k, v, causal=True, impl=dense_impl, mesh=mesh), {}
    if t % cfg.block_size:
        raise ValueError(f"sequence {t} is not a multiple of block_size {cfg.block_size}")
    impl = resolve_impl(impl)
    if impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the sparse kernels run on one device: their shard_map is not written"
        )
    interpret = impl == "pallas" and not _on_tpu()
    _telemetry.counter("sparse_attn.mode", mode="sparse").add()
    _telemetry.counter("sparse_attn", impl=impl).add()
    if impl == "pallas":
        _telemetry.counter("sparse_attn", interpret=str(interpret).lower()).add()
    with jax.named_scope("select"):
        words = select_blocks(q, k, cfg)
    words = checkpoint_name(words, "sparse_choice")
    with jax.named_scope("sparse"):
        o = _sparse(
            q.reshape(b, t, h * d), k.reshape(b, t, hkv * d),
            v.reshape(b, t, hkv * d), words, cfg, impl, interpret,
        )
        n, bk = _tiling(t, cfg.block_size)
        runs = _lists(_membership(words, t, cfg, n, bk))[1]
    return o.reshape(q.shape), {
        "kv_blocks": runs.astype(jnp.float32).mean() * (bk // cfg.block_size)
    }
