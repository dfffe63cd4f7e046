"""Attention ops: reference implementation + implementation dispatcher.

The reference framework (/root/reference) contains no attention code at all —
its models come from torchvision/HF (BASELINE configs).  This framework ships
its own TPU-native model stack (:mod:`torchdistx_tpu.models`), so attention is
a first-class op with three interchangeable implementations:

* ``"jnp"``     — pure jax.numpy reference (runs anywhere, XLA-fused);
* ``"pallas"``  — fused flash-attention Pallas TPU kernel
  (:mod:`torchdistx_tpu.ops.pallas.flash_attention`): O(seq) memory, tiled
  for the MXU, online softmax;
* ``"ring"``    — ring attention over a sequence-parallel mesh axis
  (:mod:`torchdistx_tpu.parallel.ring_attention`): blockwise attention with
  K/V rotating over ICI via ``ppermute``, for sequences too long for one
  chip's HBM.

``attention()`` picks automatically: ring iff a sequence-parallel mesh axis
is given, else pallas on TPU, else jnp.
"""

from __future__ import annotations

import functools
from typing import Optional

from .. import telemetry as _telemetry

__all__ = [
    "attention",
    "cached_attention",
    "mha_reference",
    "paged_attention",
    "paged_write_index",
]


def _neg_inf(dtype):
    import jax.numpy as jnp

    return jnp.finfo(dtype).min


def mha_reference(q, k, v, *, causal: bool = True, segment_ids=None,
                  window: Optional[int] = None):
    """Reference multi-head attention (GQA-aware) in plain jax.numpy.

    ``window=W`` (with ``causal``): key ``j`` is visible to query ``t`` iff
    ``0 <= t - j < W``, by an explicit ``(Sq, Sk)`` mask.

    Shapes: q ``(B, Sq, Hq, D)``; k ``(B, Sk, Hkv, D)``; v ``(B, Sk, Hkv,
    Dv)`` (``Dv`` = ``D`` everywhere but in latent attention) with
    ``Hq % Hkv == 0`` (grouped-query attention).  Returns ``(B, Sq, Hq, Dv)``.
    Softmax is computed in float32 regardless of input dtype (bfloat16-safe).
    """
    import jax.numpy as jnp

    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    groups = hq // hkv
    qg = q.reshape(b, sq, hkv, groups, d)
    scale = 1.0 / (d**0.5)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    if causal:
        # Positions are global: with sequence parallelism the caller passes
        # pre-offset index vectors via segment_ids=None + explicit masks in
        # ring_attention; here q and k start at 0.
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(sk)[None, :]
        mask = qi >= ki
        if window is not None:
            mask &= qi - ki < window
        logits = jnp.where(mask[None, None, None], logits, _neg_inf(jnp.float32))
    if segment_ids is not None:
        q_seg, k_seg = segment_ids
        mask = q_seg[:, None, None, :, None] == k_seg[:, None, None, None, :]
        logits = jnp.where(mask, logits, _neg_inf(jnp.float32))
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, v.shape[-1])


def _attend_cached(q, k_cache, v_cache, valid):
    """Shared decode-attention math: GQA einsum + f32 softmax over a cache.

    ``valid`` broadcasts against the f32 logits ``(B, T, Hkv, G, Sk)``.
    One definition for the contiguous (:func:`cached_attention`) and paged
    (:func:`paged_attention`) cache layouts — identical contraction and
    masking ops, so serving logits cannot drift from the generate path.
    """
    import jax.numpy as jnp

    b, t, hq, d = q.shape
    hkv = k_cache.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, t, hkv, groups, d)
    scale = 1.0 / (d**0.5)
    logits = (
        jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_cache).astype(jnp.float32)
        * scale
    )
    logits = jnp.where(valid, logits, _neg_inf(jnp.float32))
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, t, hq, d)


def cached_attention(q, k_cache, v_cache, pos):
    """Decode-time attention against a static-shape KV cache.

    q ``(B, T, Hq, D)`` holds queries for positions ``pos .. pos+T-1``;
    k/v caches ``(B, Smax, Hkv, D)`` are valid up to ``pos+T``.  Key ``j``
    attends to query ``i`` iff ``j <= pos + i`` (global causal mask over the
    cache; invalid tail masked out).  Static shapes → one compiled decode
    step regardless of position.
    """
    import jax.numpy as jnp

    t = q.shape[1]
    smax = k_cache.shape[1]
    valid = jnp.arange(smax)[None, :] <= (pos + jnp.arange(t))[:, None]
    return _attend_cached(
        q, k_cache, v_cache, valid[None, :, None, None, :]
    )


def paged_write_index(block_tables, positions, block_size):
    """Page/offset each token writes to: ``(blk, off)``, int32, shaped
    like ``positions``.

    ``positions`` may be ``(B,)`` — each slot's ONE decode token — or
    ``(B, T)`` — a chunked-prefill block of ``T`` suffix tokens per
    slot, positions ``start_b .. start_b+T-1`` (the chunked ``write_prompt``
    scatter rides this same rule).

    The ONE definition of the paged cache's write-steering rule, shared
    by every family's ``forward_paged`` (llama, gpt2) and the prefill
    scatter (``serving.cache.write_prompt``, table broadcast per
    position) — it is safety-critical for cache isolation, so it must
    not fork per call site:
    a position that has run past its table (``pos//bs >= M``)
    steers into page 0, the trash page the serving allocator never hands
    out (:data:`torchdistx_tpu.serving.blocks.TRASH_BLOCK`), so a
    retired-but-still-batched slot (or a chunk's padding tail) can never
    scribble on a live slot's pages.
    """
    import jax.numpy as jnp

    m = block_tables.shape[1]
    blk_no = positions // block_size
    if positions.ndim == 1:
        blk = jnp.take_along_axis(
            block_tables, jnp.clip(blk_no, 0, m - 1)[:, None], axis=1
        )[:, 0]
    else:  # (B, T): T gathers per slot from its own table row
        blk = jnp.take_along_axis(
            block_tables, jnp.clip(blk_no, 0, m - 1), axis=1
        )
    blk = jnp.where(blk_no < m, blk, 0)
    return blk, positions % block_size


def paged_attention(q, k_pages, v_pages, block_tables, positions):
    """Decode-time attention against a block/paged KV cache (serving path).

    q ``(B, T, Hq, D)`` holds slot ``b``'s queries for positions
    ``positions[b] .. positions[b]+T-1`` — ``T == 1`` is a decode step;
    ``T > 1`` is a chunked-prefill block attending the slot's cached
    prefix (shared pages included) plus itself, the partial-prefix
    attention of the prefix cache.  ``k_pages``/``v_pages``
    ``(NB, bs, Hkv, D)`` are the one-layer page pools; ``block_tables``
    ``(B, M)`` int32 maps slot ``b``'s logical block ``j`` to its page.
    Gathers each slot's pages into a contiguous ``(B, M*bs, Hkv, D)`` view
    and reuses :func:`_attend_cached` with the per-slot causal mask
    ``key j <= positions[b] + i`` — pages beyond a slot's history (and the
    shared trash page other slots scribble on) mask to exactly-zero
    probability, so values match the contiguous-cache path bit-for-bit.

    The gather reads ``M*bs`` positions per slot; size ``M`` (the engine's
    ``max_model_len``) to the longest admissible request, NOT the model's
    ``max_seq_len`` — that width, not the pool size, is the decode-step
    HBM traffic.
    """
    import jax.numpy as jnp

    b, t = q.shape[0], q.shape[1]
    nb, bs, hkv, d = k_pages.shape
    m = block_tables.shape[1]
    k = jnp.take(k_pages, block_tables, axis=0).reshape(b, m * bs, hkv, d)
    v = jnp.take(v_pages, block_tables, axis=0).reshape(b, m * bs, hkv, d)
    valid = (
        jnp.arange(m * bs)[None, None, :]
        <= (positions[:, None] + jnp.arange(t)[None, :])[:, :, None]
    )
    return _attend_cached(q, k, v, valid[:, :, None, None, :])


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # A backend that fails to initialize raises here (and is not cached):
    # "no TPU" must never be inferred from an error, or impl="auto" would
    # train on the jnp path and still report a loss.
    import jax

    return jax.devices()[0].platform == "tpu"


def attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    impl: str = "auto",
    mesh=None,
    seq_axis: Optional[str] = None,
    pre_permuted: bool = False,
    window: Optional[int] = None,
):
    """Dispatching attention entry point used by the model stack.

    ``window=W`` (static; needs ``causal``): key ``j`` is visible to query
    ``t`` iff ``0 <= t - j < W``.  ``jnp`` masks it; ``pallas`` runs the
    banded kernels (``flash_win_*``), which compute and fetch the band's
    blocks only; the ring implementations know no window and raise.

    ``impl``: ``"auto" | "jnp" | "pallas" | "ring" | "ring_zigzag"``.
    ``auto`` = ring iff ``seq_axis`` is set (sequence/context parallelism);
    else the Pallas flash kernel on TPU — single-chip directly, under a
    mesh via its shard_map wrapper (batch over dp/fsdp, heads over tp; see
    :func:`~torchdistx_tpu.ops.pallas.flash_attention.flash_attention_sharded`)
    whenever the shapes divide over the mesh; else jnp (XLA-fused,
    partitions anywhere).  ``ring_zigzag`` is the load-balanced causal ring
    schedule (see :mod:`torchdistx_tpu.parallel.ring_attention`).

    Callers already *inside* a shard_map (the pipeline stage body) must not
    select ``"pallas"`` with a mesh — the model forwards pin ``"jnp"``
    under ``pp_axis``.
    """
    from .pallas.flash_attention import _check_window

    _check_window(window, causal)
    impl = _select_impl(impl, mesh, seq_axis, q.shape, k.shape)
    # The resolved choice, counted per trace: a quiet downgrade of "auto"
    # (jnp where the kernel was expected) shows in the counters.
    _telemetry.counter("attention.dispatch", impl=impl).add()
    if impl in ("ring", "ring_zigzag"):
        from ..parallel.ring_attention import ring_attention

        if mesh is None or seq_axis is None:
            raise ValueError("ring attention needs mesh= and seq_axis=")
        if window is not None:
            raise NotImplementedError(
                "ring attention knows no window; use impl='pallas' or 'jnp'"
            )
        return ring_attention(
            q, k, v, mesh=mesh, axis=seq_axis, causal=causal,
            schedule="zigzag" if impl == "ring_zigzag" else "contiguous",
            pre_permuted=pre_permuted,
        )
    if pre_permuted:
        raise ValueError("pre_permuted is only meaningful with ring_zigzag")
    if impl == "pallas":
        from .pallas.flash_attention import (
            flash_attention,
            flash_attention_sharded,
            shardable,
        )

        if mesh is not None and shardable(mesh, q.shape, k.shape):
            return flash_attention_sharded(
                q, k, v, causal=causal, mesh=mesh, window=window
            )
        # mesh=None, or an explicit "pallas" opt-in whose shapes don't divide
        # over the mesh: the bare kernel (replicated per chip under a mesh —
        # the long-documented escape hatch for replicated heads/batch).
        return flash_attention(q, k, v, causal=causal, window=window)
    if impl != "jnp":
        raise ValueError(
            f"unknown attention impl: {impl!r} "
            "(expected auto|jnp|pallas|ring|ring_zigzag)"
        )
    return mha_reference(q, k, v, causal=causal, window=window)


# Mesh axes the shard_map wrapper understands: dp/fsdp shard batch, tp
# shards heads, and activations are replicated over ep/pp at the point
# attention runs (expert dispatch and pipeline staging have their own
# shard_maps elsewhere).  A mesh with any OTHER nontrivial axis (custom
# names like "data"/"model") falls back to jnp — a bare Mosaic call can't
# partition over axes we don't recognize.
_KNOWN_AXES = frozenset({"dp", "fsdp", "tp", "ep", "pp"})


def _select_impl(impl, mesh, seq_axis, q_shape, kv_shape) -> str:
    """Resolve ``impl="auto"`` (factored out for direct testing)."""
    if impl != "auto":
        return impl
    if seq_axis is not None:
        return "ring"
    if not _on_tpu():
        return "jnp"
    if mesh is None:
        return "pallas"
    if any(
        size > 1 and name not in _KNOWN_AXES
        for name, size in mesh.shape.items()
    ):
        return "jnp"
    from .pallas.flash_attention import shardable

    # Under a mesh the kernel runs through its shard_map wrapper; shapes
    # that don't divide over the mesh (odd batch vs dp, GQA heads vs tp)
    # fall back to XLA's fused jnp path, which partitions anything.
    return "pallas" if shardable(mesh, q_shape, kv_shape) else "jnp"


def resolve_stage_attn_impl(attn_impl: str) -> str:
    """Pin the attention impl for code already inside a pipeline stage.

    Stage bodies run inside the pipeline's shard_map; the flash kernel's
    own shard_map wrapper cannot nest there, so ``"auto"`` pins to
    ``"jnp"`` and an explicit ``"pallas"`` is refused.  Shared by every
    model family's ``forward`` (llama/gpt2/moe).
    """
    if attn_impl == "pallas":
        raise ValueError(
            "attn_impl='pallas' cannot run inside a pipeline stage; "
            "use 'auto' or 'jnp'"
        )
    return "jnp" if attn_impl == "auto" else attn_impl
