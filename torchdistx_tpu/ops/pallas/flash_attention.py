"""Fused flash attention — Pallas TPU kernels (forward + backward).

Single-chip attention for the model stack (:mod:`torchdistx_tpu.models`).
Both Q **and** K/V are tiled: the kv dimension is a grid axis streamed
through VMEM with online-softmax accumulators held in VMEM scratch, so
per-step VMEM is O(bq·d + bkv·d) regardless of sequence length — the
long-context regime (S ≥ 16k) the kernel exists for.  Matmuls keep their
storage dtype (bf16 → full MXU rate) and accumulate in f32 via
``preferred_element_type``; logits/softmax/rescale math runs in float32 on
the VPU.  GQA is handled in the index maps — each
Q-head grid step fetches its kv-head's K/V block (no materialized head
expansion, no extra HBM traffic).

Perf notes (v5e, S=16k, d=128): blocks default to 1024 — large blocks
amortize per-grid-step overhead and quadrupled throughput over 256-blocks;
softmax runs in the log2 domain (``exp2`` is the native transcendental,
log2 e folds into the softmax scale); only padded kv cols and
causal-diagonal blocks are masked (padded q rows cancel structurally).
Together: fwd+bwd 61→22 ms, attention MFU 0.16→0.44.

The backward uses the standard flash-attention gradient identities
(dv = pᵀ·do, ds = p∘(do·vᵀ − rowsum(do∘o)), dq = ds·k, dk = dsᵀ·q).  The
shapes alone choose its kernels (``_fa_backward``; counted per trace as
``attention.flash_bwd{kernel=}``):

* one kv block (S ≤ 2048, the training regime): ONE kernel, grid
  ``(B, Hkv, groups·nq)`` — dq is complete after each grid step;
* several kv blocks: still ONE kernel, grid ``(B, Hkv, nkv, groups·nq)``,
  the sequence's float32 dq held in VMEM across the kv blocks — while it
  fits ``_FUSED_BWD_DQ_VMEM``;
* past that, two kernels, each streaming its reduction axis through a grid
  dimension with VMEM scratch accumulators, so VMEM stays O(bq·d + bkv·d):
  dq, grid ``(B, Hq, nq, nkv)``, and dk/dv, grid
  ``(B, Hkv, nkv, groups·nq)``.  The pair forms p and ds twice.

dk/dv always sum the GQA group reduction in-kernel.

``window=W`` (static, with ``causal``): key ``j`` is visible to query ``t``
iff ``0 <= t - j < W``.  The kernels are the same bodies with one more
bound, under names of their own (``flash_win_fwd``, ``flash_win_bwd_fused``,
``flash_win_bwd_dq``, ``flash_win_bwd_dkv``; counted as
``attention.flash_window{window=}`` and ``attention.flash_bwd{kernel=
win_*}``):

* what RUNS: a (q block, kv block) pair that holds a visible pair, i.e.
  the kv block starts at or before the q block's last row and ends after
  the first row's lower edge.  Those are an interval of kv blocks a q
  block (and of q blocks a kv block), and the streamed grid axis of a
  window call spans that interval, not the sequence: ``ceil(W / block) +
  1`` steps at square blocks, 3 at 1,024-blocks and ``W`` = 2,048
  whatever the length (histogram ``attention.window_kv_blocks``);
* what is MASKED: only a block that crosses the diagonal, the band's
  lower edge or the padding; a block wholly inside the band keeps the
  mask-free body;
* what is FETCHED: the band's blocks; a step past the band's end (the
  first q blocks meet fewer kv blocks than the axis has steps) is clamped
  to the last block, so nothing is copied for it.

With ``window=None`` every kernel is traced to the operations it had
before windows existed; a window that holds the whole sequence is plain
causal attention and takes the plain kernels.

Sequence lengths are padded to the TPU tile grain (128, or 8 below one
block); padded keys/queries are masked in-kernel, so any length is accepted.
The log-sum-exp/delta tensors are carried as ``(B, H, S_pad, 1)`` so their
``(1, 1, bq, 1)`` blocks satisfy Mosaic's (8, 128)-or-equal tiling rule on
the last two block dims (the round-1 ``(1, 1, bq)`` spec did not compile on
real TPU).

``interpret=True`` runs the same kernels through the Pallas interpreter so
CPU CI (the virtual-mesh test rig, SURVEY.md §4) covers the kernel logic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ... import telemetry as _telemetry

__all__ = [
    "flash_attention", "flash_attention_sharded", "shardable",
]

# Finite "minus infinity": keeps the online-softmax recurrences NaN-free for
# rows whose valid keys haven't streamed in yet (exp(-1e30 − m) underflows to
# exactly 0; -inf would produce inf−inf = NaN in the rescale term).
_MASK = -1e30


def _pad_len(s: int) -> int:
    """Sequence padded to the TPU tile grain."""
    if s >= 128:
        return -(-s // 128) * 128
    return -(-s // 8) * 8


# Block-size overrides (None = the defaults below).  Tests set them to
# get several q and kv blocks at small sizes (``tests/test_attention.py``);
# nothing else does.
#
# They are read at TRACE time and are not part of any jit cache key: a
# function already traced under a caller's ``jax.jit`` keeps the blocks it
# was traced with.  Whoever sets one traces anew afterwards (a fresh
# ``jax.jit``, or ``jax.clear_caches()``) and restores it when done
# (``monkeypatch`` does).
#
# With the mask-free interior bodies, SQUARE blocks measure best both
# directions at S=16k d=128 on v5e (adjacent same-window runs:
# fwd+bwd 23.5 ms at the round-4 (512,2048)/(512,1024) defaults →
# 21.9 ms with fwd 1024² → 20.6 ms with bwd 1024² as well): at bq == bkv
# exactly one kv step per q block pays the masked body, and the square
# shape balances the dq/dkv accumulator footprints.
_BWD_BLOCK_Q = None
_BWD_BLOCK_KV = None
_BWD_BLOCK_Q_DEFAULT = 1024
_BWD_BLOCK_KV_DEFAULT = 1024
# Sequences up to this length take the fused one-kernel backward with the
# whole kv extent as a single block (VMEM bound: the (bq, s_pad) f32
# p/ds buffers — 8 MB at bq 1024, s 2048).  Beyond it, the streamed
# two-kernel backward.
_FUSED_BWD_MAX_KV = 2048
# Known-good f32 working-set budget for one (bq, bkv) p/ds pair in the
# fused backward (1024² — the S=1024 training case); bq 1024 × bkv 2048
# runs out of VMEM (Mosaic, libtpu 0.0.34, v5e: RESOURCE_EXHAUSTED at
# compile time, as does 16 MB at S=4096).  The fused path halves bq down
# to 128 to stay under this, and falls back to the streamed two-kernel
# backward when even bq=128 cannot fit (bkv = s_pad > 8192).
_FUSED_BWD_VMEM_CAP = 1024 * 1024 * 4
# Past one kv block the backward stays ONE kernel while the whole
# sequence's f32 dq accumulator, (groups, s_pad, d_qk padded to 128
# lanes), fits this much VMEM beside the blocks (a v5e core has 128 MiB;
# 8 MiB at 8,192 positions of 192-wide heads, 16 MiB at 32k of 128).
# Longer sequences and wider GQA groups take the streamed pair.
_FUSED_BWD_DQ_VMEM = 1024 * 1024 * 16
_FWD_BLOCK_Q = None
_FWD_BLOCK_KV = None
_FWD_BLOCK_Q_DEFAULT = 1024
_FWD_BLOCK_KV_DEFAULT = 1024


def _pick_block(s_pad: int, override, default) -> int:
    for cand in (override, default):
        if cand and s_pad % cand == 0:
            return cand
    return _block_for(s_pad)


def _block_for(s_pad: int, preferred: int = 1024) -> int:
    # Large blocks amortize per-grid-step overhead (DMA issue, softmax VPU
    # setup): at S=16k, d=128, blocks of 1024 run the fwd+bwd pair 2.5×
    # faster than 256 (27ms vs 68ms, v5e).  2048 exceeds VMEM with
    # double-buffered q/k/v/o + f32 scratch.
    for b in (preferred, 512, 256, 128):
        if s_pad % b == 0:
            return b
    return s_pad  # s_pad < 128: single block (equality escape in Mosaic)


# exp(x) = exp2(x·log2 e): exp2 is the native TPU transcendental, and the
# log2 e factor folds into the softmax scale (fwd) or a single multiply
# (bwd), shaving VPU work from the hottest loop.
_LOG2E = 1.4426950408889634


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _diag_clamp(causal: bool, bq: int, bkv: int, clamp):
    """Index transform for the *streamed* block axis of a causal grid.

    Blocks strictly on the skipped side of the diagonal are never computed
    (the kernels' ``run`` predicate ``q_start + bq - 1 >= k_start``);
    clamping their index to the diagonal makes consecutive grid steps
    fetch the same block, and Mosaic elides the repeated HBM→VMEM copy —
    at 16k that is half the streamed-side traffic.  ``clamp`` is
    ``jnp.minimum`` for a streamed kv axis (skip blocks past the last
    running kv block of the fixed q row) and ``jnp.maximum`` for a
    streamed q axis (skip blocks before the first running q block of the
    fixed kv row); both reduce to min/max(streamed, fixed) when
    ``bq == bkv``.
    """
    if not causal:
        return lambda streamed, fixed: streamed
    if clamp is jnp.minimum:
        return lambda ki, qi: jnp.minimum(ki, (qi * bq + bq - 1) // bkv)
    return lambda qi, ki: jnp.maximum(qi, (ki * bkv) // bq)


def _stream_kv(causal, bq, bkv, window):
    """The kv block a streamed kv axis fetches at ``(step, qi)``: the
    diagonal's clamp, and with a window the band's first block plus the
    step, clamped to its last."""
    if window is None:
        return _diag_clamp(causal, bq, bkv, jnp.minimum)

    def index(step, qi):
        first, last = _kv_band(qi, bq=bq, bkv=bkv, window=window)
        return jnp.minimum(first + step, last)

    return index


# Bounds of ``attention.window_kv_blocks``: kv blocks ONE q block of a
# window forward runs (its streamed grid axis).
_BAND_BOUNDS = tuple(float(i) for i in range(1, 33))


# ---------------------------------------------------------------------------
# The band of a window call (module docstring).  A (q block, kv block) pair
# holds a visible pair iff ``q_start + bq - 1 >= k_start`` (the diagonal)
# AND ``k_start + bkv - 1 > q_start - window`` (the lower edge).  Both are
# monotone, so the blocks that run are an interval along either axis.


def _kv_band(qi, *, bq, bkv, window, mx=jnp.maximum):
    """``(first, last)`` kv block q block ``qi`` meets (``mx=max`` on
    Python ints)."""
    return mx(qi * bq - window + 1, 0) // bkv, (qi * bq + bq - 1) // bkv


def _q_band(ki, *, bq, bkv, window, nq, mn=jnp.minimum):
    """``(first, last)`` q block kv block ``ki`` meets (``mn=min`` on
    Python ints)."""
    return (ki * bkv) // bq, mn((ki * bkv + bkv + window - 2) // bq, nq - 1)


def _kv_band_steps(nq, **blocks) -> int:
    """Steps of a streamed kv axis that spans the band."""
    return max(
        last - first + 1
        for first, last in (_kv_band(qi, mx=max, **blocks) for qi in range(nq))
    )


def _q_band_steps(nk, nq, **blocks) -> int:
    """Steps of a streamed q axis that spans the band."""
    return max(
        last - first + 1
        for first, last in (
            _q_band(ki, nq=nq, mn=min, **blocks) for ki in range(nk)
        )
    )


def _band_q_block(idx, ki, *, bq, bkv, nq, window, nqb):
    """The q block of step ``idx`` of a kv-major grid's inner axis, which
    runs over (gqa group, q block) pairs: ``(qi, in_range)``.  With a
    window the axis holds ``nqb`` q blocks a group, counted from the first
    of kv block ``ki``'s band, and a step past the sequence's last q block
    is out of range (``in_range`` is None where every step is in it)."""
    if window is None:
        return idx % nq, None
    qi = _q_band(ki, bq=bq, bkv=bkv, window=window, nq=nq)[0] + idx % nqb
    return qi, qi < nq


def _runs(causal, window, q_start, k_start, bq, bkv):
    """Whether a block pair holds a visible (query, key) pair."""
    if not causal:
        return True
    run = q_start + bq - 1 >= k_start
    if window is not None:
        run &= k_start + bkv - 1 + window > q_start
    return run


# ---------------------------------------------------------------------------
# Forward


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, bq, bkv, s, window=None,
):
    import jax.experimental.pallas as pl

    qi = pl.program_id(2)
    step = pl.program_id(3)
    nk = pl.num_programs(3)
    # With a window the streamed axis counts from the band's first block.
    ki = step
    if window is not None:
        ki = _kv_band(qi, bq=bq, bkv=bkv, window=window)[0] + step
    q_start = qi * bq
    k_start = ki * bkv

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: skip kv blocks entirely above the diagonal (and, with a
    # window, entirely below the band).
    run = _runs(causal, window, q_start, k_start, bq, bkv)
    # Masking is needed only where correctness demands it: the one kv
    # step whose block intersects the causal diagonal (bq ≤ bkv ⇒ at most
    # one per q block), the band's lower edge, or carries padded cols.
    # Everything below runs the mask-free body — the iota/compare/select passes are ~1/3 of the
    # per-step VPU element work and ~90% of steps don't need them.  The
    # two bodies are scalar-branched with pl.when (a real Mosaic branch;
    # a lax.cond variant measured slower).
    needs_mask = _needs_mask(causal, q_start, k_start, bkv, s, bq, window)

    def _body(apply_mask):
        # Matmul inputs keep their storage dtype (bf16 on TPU → full MXU
        # rate) with f32 accumulation; only softmax math runs f32 on the
        # VPU.  An earlier revision upcast to f32 *before* the dots, which
        # quarters MXU throughput.  Softmax runs in the log2 domain (scale
        # folds in log2 e; exp2 is the native transcendental).
        q = q_ref[0, 0]  # (bq, d)
        logits = (
            jax.lax.dot_general(
                q, k_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * (scale * _LOG2E)
        )
        if apply_mask:
            # Mask only what correctness needs: padded kv cols (they must
            # not enter l), the causal triangle when the block touches
            # the diagonal.  Padded q ROWS need no mask: their logits are
            # finite (zero-padded q) and their outputs are sliced off.
            # A row whose part of the band's first block is all masked
            # leaves ``l`` and ``acc`` wrong there (``exp2(_MASK - _MASK)``
            # is 1); its own diagonal block, which comes later, rescales
            # both by ``exp2(_MASK - m)`` = 0.
            kpos = k_start + _iota((bq, bkv), 1)
            keep = kpos < s
            if causal:
                qpos = q_start + _iota((bq, bkv), 0)
                keep &= qpos >= kpos
                if window is not None:
                    keep &= qpos - kpos < window
            logits = jnp.where(keep, logits, _MASK)

        # Row statistics computed on (bq, 1) slices: the scratch tiles are
        # physically (bq, 128) (f32 tiling grain), but running the
        # max/exp/rescale math lane-replicated would add bq·128 exps per
        # step — a ~50% increase over the bq·bkv softmax exps themselves.
        #
        # Rejected variants, measured at S=16k (v5e): in-body kv
        # sub-splitting with a combined max (no MXU/VPU overlap — Mosaic
        # barriers every exp2 behind all qk matmuls), per-sub online
        # updates (extra acc rescales), lax.cond-gated masking
        # (predication costs more than the iota/where it saves, 10.6 →
        # 13.7 ms).  The win that stuck is the scalar-branched mask-free
        # interior body (see pl.when below).
        m_prev = m_ref[...][:, :1]  # (bq, 1)
        l_prev = l_ref[...][:, :1]
        row_max = jnp.max(logits, axis=-1, keepdims=True)  # (bq, 1)
        m_next = jnp.maximum(m_prev, row_max)
        alpha = jnp.exp2(m_prev - m_next)  # (bq, 1)
        p = jnp.exp2(logits - m_next)  # (bq, bkv)
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(run & needs_mask)
    def _body_masked():
        _body(True)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _body_plain():
        _body(False)

    @pl.when(step == nk - 1)
    def _finish():
        l = l_ref[...][:, :1]  # (bq, 1)
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padded) rows
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # m is tracked in the log2 domain; lse stays natural-log (the
        # backward converts once per row block).
        lse_ref[0, 0] = m_ref[...][:, :1] / _LOG2E + jnp.log(l_safe)


def _fa_forward_padded(
    q, k, v, s, *, causal: bool, interpret: bool, window=None
):
    """q: (B, Hq, S_pad, D); k: (B, Hkv, S_pad, D); v: (B, Hkv, S_pad, Dv);
    ``s`` = valid length.  ``Dv`` may differ from ``D`` (latent attention:
    192-wide q and k against 128-wide v); the scale is ``D**-0.5``.

    Returns ``(out, lse)`` with ``out`` ``(B, Hq, S_pad, Dv)`` and ``lse``
    ``(B, Hq, S_pad, 1)`` float32.
    """
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, hq, s_pad, d = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    groups = hq // hkv
    bq = _pick_block(s_pad, _FWD_BLOCK_Q, _FWD_BLOCK_Q_DEFAULT)
    bkv = _pick_block(s_pad, _FWD_BLOCK_KV, _FWD_BLOCK_KV_DEFAULT)
    nq, nk = s_pad // bq, s_pad // bkv
    scale = 1.0 / (d**0.5)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv, s=s,
        window=window,
    )

    kv_clamp = _stream_kv(causal, bq, bkv, window)
    if window is not None:
        nk = _kv_band_steps(nq, bq=bq, bkv=bkv, window=window)
        _telemetry.histogram(
            "attention.window_kv_blocks", _BAND_BOUNDS
        ).observe(nk)

    def kv_index(bi, hi, qi, ki, g=groups):
        return (bi, hi // g, kv_clamp(ki, qi), 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d), kv_index),
            pl.BlockSpec((1, 1, bkv, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec(
                (1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, s_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd" if window is None else "flash_win_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward


def _recompute_p(
    q, k, lse, q_start, k_start, *, scale, causal, bq, bkv, s, s_pad,
    apply_mask=True, window=None,
):
    """Recompute the softmax block from the saved (natural-log) lse.

    Masking needed: the causal triangle on diagonal blocks (interior
    blocks lie fully below it), and — non-causal with padding only — the
    padded kv cols, whose p = exp(-lse) can overflow f32 for very negative
    lse and then poison dq with inf·0 = NaN.  (Causal padding is safe: for
    real rows every padded col sits above the diagonal; padded q-row /
    kv-col contributions otherwise cancel against zero-padded do/k/v, and
    padded dk/dv rows are sliced off by the caller.)

    ``apply_mask=False`` skips the iota/compare/select passes — callers
    branch on the same block-level condition the forward uses (at most one
    kv block per q block intersects the diagonal).
    """
    logits = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * (scale * _LOG2E)
    )
    p = jnp.exp2(logits - lse * _LOG2E)
    if not apply_mask:
        return p
    if causal:
        kpos = k_start + _iota((bq, bkv), 1)
        qpos = q_start + _iota((bq, bkv), 0)
        keep = qpos >= kpos
        if window is not None:
            # A padded q row that sees no key at all has an lse near
            # ``_MASK`` and p = inf here; it only ever meets masked
            # blocks (a mask-free block is visible to all its rows), and
            # the select drops it.
            keep &= qpos - kpos < window
        p = jnp.where(keep, p, 0.0)
    elif s_pad > s:
        kpos = k_start + _iota((bq, bkv), 1)
        p = jnp.where(kpos < s, p, 0.0)
    return p


def _needs_mask(causal, q_start, k_start, bkv, s, bq=None, window=None):
    """Block-level mask condition shared by fwd and bwd kernels: the kv
    block crosses the causal diagonal for this q block, or carries padded
    cols, or (with a window) crosses the band's lower edge.  (Worst causal
    pair: first q row vs last kv col; worst pair of the band: last q row
    vs first kv col.)"""
    needs = k_start + bkv > s
    if causal:
        needs |= k_start + bkv - 1 > q_start
        if window is not None:
            needs |= q_start + bq - 1 - k_start >= window
    return needs


def _p_ds(
    q, k, v, do, lse, delta, q_start, k_start,
    *, scale, causal, bq, bkv, s, s_pad, apply_mask, window=None,
):
    """The shared backward block chain: recomputed softmax ``p`` and the
    logit gradient ``ds = p ∘ (do·vᵀ − Δ)·scale`` (cast to the matmul
    dtype).  Every backward kernel (dq, dk/dv, fused) consumes exactly
    these two — one definition so a change to the gradient identities
    cannot silently diverge between the long-context and training paths.
    """
    p = _recompute_p(
        q, k, lse, q_start, k_start,
        scale=scale, causal=causal, bq=bq, bkv=bkv, s=s, s_pad=s_pad,
        apply_mask=apply_mask, window=window,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    return p, ds


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, scale, causal, bq, bkv, s, s_pad, window=None,
):
    import jax.experimental.pallas as pl

    qi = pl.program_id(2)
    step = pl.program_id(3)
    nk = pl.num_programs(3)
    ki = step  # with a window: counted from the band's first block
    if window is not None:
        ki = _kv_band(qi, bq=bq, bkv=bkv, window=window)[0] + step
    q_start = qi * bq
    k_start = ki * bkv

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = _runs(causal, window, q_start, k_start, bq, bkv)
    needs_mask = _needs_mask(causal, q_start, k_start, bkv, s, bq, window)

    def _body(apply_mask):
        # bf16 matmul inputs + f32 accumulation (see _fwd_kernel note).
        _, ds = _p_ds(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0], delta_ref[0, 0], q_start, k_start,
            scale=scale, causal=causal, bq=bq, bkv=bkv, s=s, s_pad=s_pad,
            apply_mask=apply_mask, window=window,
        )
        acc_ref[...] += jax.lax.dot_general(
            ds, k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(run & needs_mask)
    def _body_masked():
        _body(True)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _body_plain():
        _body(False)

    @pl.when(step == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, causal, bq, bkv, s, s_pad, nq, window=None,
    nqb=None,
):
    import jax.experimental.pallas as pl

    ki = pl.program_id(2)
    idx = pl.program_id(3)  # (gqa group, q block) pairs
    n_idx = pl.num_programs(3)
    qi, in_range = _band_q_block(
        idx, ki, bq=bq, bkv=bkv, nq=nq, window=window, nqb=nqb
    )
    q_start = qi * bq
    k_start = ki * bkv

    @pl.when(idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = _runs(causal, window, q_start, k_start, bq, bkv)
    if in_range is not None:
        run &= in_range
    needs_mask = _needs_mask(causal, q_start, k_start, bkv, s, bq, window)

    def _body(apply_mask):
        # bf16 matmul inputs + f32 accumulation (see _fwd_kernel note).
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        p, ds = _p_ds(
            q, k_ref[0, 0], v_ref[0, 0], do, lse_ref[0, 0],
            delta_ref[0, 0], q_start, k_start,
            scale=scale, causal=causal, bq=bq, bkv=bkv, s=s, s_pad=s_pad,
            apply_mask=apply_mask, window=window,
        )
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(run & needs_mask)
    def _body_masked():
        _body(True)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _body_plain():
        _body(False)

    @pl.when(idx == n_idx - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dqkv_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref,
    dv_ref, dk_acc, dv_acc,
    *, scale, causal, bq, bkv, s, s_pad, nq, window=None,
):
    """Single-kv-block backward (``nk == 1`` — the training regime, where
    S fits one kv block): dq, dk, dv in ONE kernel.

    With the whole kv extent resident, dq needs no cross-step
    accumulation (each q block's dq is complete after its own grid step),
    so the classic dq/dkv grid-order conflict disappears.  One kernel
    halves the per-layer pallas-call count AND computes the p/dp
    recompute once instead of twice (5 block matmuls instead of 7, half
    the bwd exp2s) — the two-kernel split at S=1024/d=64 measured ~0.64
    ms per call with ~0.09 ms of ideal matmul work, i.e. per-call
    overhead and duplicated softmax dominated the training backward.
    """
    import jax.experimental.pallas as pl

    idx = pl.program_id(2)  # (gqa group, q block) pairs
    n_idx = pl.num_programs(2)
    qi = idx % nq
    q_start = qi * bq
    k_start = 0

    @pl.when(idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # The one kv block is the whole sequence: it reaches into every band.
    run = (q_start + bq - 1 >= k_start) if causal else True
    needs_mask = _needs_mask(causal, q_start, k_start, bkv, s, bq, window)

    def _body(apply_mask):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        do = do_ref[0, 0]
        p, ds = _p_ds(
            q, k, v_ref[0, 0], do, lse_ref[0, 0], delta_ref[0, 0],
            q_start, k_start,
            scale=scale, causal=causal, bq=bq, bkv=bkv, s=s, s_pad=s_pad,
            apply_mask=apply_mask, window=window,
        )
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_ref[0, 0] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dq_ref.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(run & needs_mask)
    def _body_masked():
        _body(True)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _body_plain():
        _body(False)

    # Above-diagonal q blocks never run the body: their dq block is pure
    # padding-free zeros.
    @pl.when(jnp.logical_not(run))
    def _zero_dq():
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(idx == n_idx - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dqkv_stream_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref,
    dv_ref, dk_acc, dv_acc, dq_acc,
    *, scale, causal, bq, bkv, s, s_pad, nq, window=None, nqb=None,
):
    """Several kv blocks, still ONE kernel: the grid is ``_dkv_kernel``'s
    (kv block outer, (gqa group, q block) pairs inner), and the whole
    sequence's dq accumulates in VMEM across the kv blocks, so p and ds
    are formed once per (q block, kv block) pair: five block products
    where the streamed pair runs seven, half its exp2s, and q, do, lse
    and delta read from HBM once.  Each q block's dq sums over the kv
    blocks in f32 in the order ``_dq_kernel`` sums them.

    With a window the inner axis spans the q blocks of this kv block's
    band: a q block's dq rows are zeroed at the first kv block of ITS band
    and leave the accumulator at the last.
    """
    import jax.experimental.pallas as pl

    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    idx = pl.program_id(3)  # (gqa group, q block) pairs
    n_idx = pl.num_programs(3)
    g = idx // (nq if window is None else nqb)
    qi, in_range = _band_q_block(
        idx, ki, bq=bq, bkv=bkv, nq=nq, window=window, nqb=nqb
    )
    q_start = qi * bq
    k_start = ki * bkv
    # When a q block's dq rows are zeroed and when they leave the
    # accumulator (thunks: traced where the plain kernel traced them).
    if window is None:
        rows = pl.ds(pl.multiple_of(q_start, bq), bq)
        first_kv, last_kv = (lambda: ki == 0), (lambda: ki == nk - 1)
    else:
        # A step past the sequence's last q block touches no row.
        rows = pl.ds(pl.multiple_of(jnp.minimum(qi, nq - 1) * bq, bq), bq)
        first, last = _kv_band(qi, bq=bq, bkv=bkv, window=window)
        first_kv = lambda: (ki == first) & in_range  # noqa: E731
        last_kv = lambda: (ki == last) & in_range  # noqa: E731

    @pl.when(idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(first_kv())
    def _init_dq():
        dq_acc[g, rows, :] = jnp.zeros((bq, dq_acc.shape[-1]), dq_acc.dtype)

    run = _runs(causal, window, q_start, k_start, bq, bkv)
    if in_range is not None:
        run &= in_range
    needs_mask = _needs_mask(causal, q_start, k_start, bkv, s, bq, window)

    def _body(apply_mask):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        do = do_ref[0, 0]
        p, ds = _p_ds(
            q, k, v_ref[0, 0], do, lse_ref[0, 0], delta_ref[0, 0],
            q_start, k_start,
            scale=scale, causal=causal, bq=bq, bkv=bkv, s=s, s_pad=s_pad,
            apply_mask=apply_mask, window=window,
        )
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[g, rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(run & needs_mask)
    def _body_masked():
        _body(True)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _body_plain():
        _body(False)

    # The dq block is the whole (groups, s_pad) extent of this kv head:
    # it leaves VMEM once, after the last kv block filled it in.
    @pl.when(last_kv())
    def _finish_dq():
        dq_ref[0, g, rows, :] = dq_acc[g, rows, :].astype(dq_ref.dtype)

    @pl.when(idx == n_idx - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_backward_fused_nk1(
    q, k, v, delta, lse, do, s, *, causal, interpret, window=None
):
    """One-kernel backward for ``s_pad <= bkv`` (single kv block)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, hq, s_pad, d = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    groups = hq // hkv
    bq = _pick_block(s_pad, _BWD_BLOCK_Q, _BWD_BLOCK_Q_DEFAULT)
    bkv = s_pad  # single block
    # Cap the (bq, bkv) f32 p/ds working set (_FUSED_BWD_VMEM_CAP) by
    # halving bq; below 128 rows the MXU tiles go partial, so once bq
    # bottoms out there the single-block premise itself has failed —
    # stream kv through a grid axis instead of holding it whole.
    while bq > 128 and bq * bkv * 4 > _FUSED_BWD_VMEM_CAP:
        bq //= 2
    if bq * bkv * 4 > _FUSED_BWD_VMEM_CAP:
        # Don't hand the streamed path our whittled bq: its kv blocks are
        # _block_for-sized, not the whole extent, so its own default q
        # block (the known-good 1024² working set) fits the cap fine —
        # a 128-row handoff would just run 8× more dq grid iterations.
        return _fa_backward_streamed(
            q, k, v, delta, lse, do, s, causal=causal, interpret=interpret,
            bkv=_block_for(s_pad), window=window,
        )
    _count_bwd("fused_nk1", window)
    nq = s_pad // bq
    scale = 1.0 / (d**0.5)

    def gq_spec(width):
        return pl.BlockSpec(
            (1, 1, bq, width),
            lambda bi, hkvi, idx, g=groups, n=nq: (
                bi, hkvi * g + idx // n, idx % n, 0
            ),
        )

    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, bkv, width), lambda bi, hkvi, idx: (bi, hkvi, 0, 0)
        )

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _dqkv_fused_kernel, scale=scale, causal=causal, bq=bq,
            bkv=bkv, s=s, s_pad=s_pad, nq=nq, window=window,
        ),
        grid=(b, hkv, groups * nq),
        in_specs=[
            gq_spec(d), kv_spec(d), kv_spec(dv), gq_spec(dv), gq_spec(1),
            gq_spec(1),
        ],
        out_specs=[gq_spec(d), kv_spec(d), kv_spec(dv)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_bwd_name("fused", window),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _lanes(d: int) -> int:
    """A minor dimension as VMEM holds it: whole 128-lane tiles."""
    return -(-d // 128) * 128


def _vmem_bytes(rows: int, cols: int, dtype) -> int:
    """A ``(rows, cols)`` block in VMEM: 128 lanes by 8 32-bit sublanes a
    tile (16 rows of bf16)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // item)
    return -(-rows // sub) * sub * _lanes(cols) * item


def _count_bwd(kernel: str, window=None):
    # Counted per trace, where the kernel is built: which backward a shape
    # took (``fused_nk1``, ``fused`` or ``pair``; ``win_fused_nk1``,
    # ``win_fused`` or ``win_pair`` with a window) shows in the counters.
    if window is not None:
        kernel = "win_" + kernel
    _telemetry.counter("attention.flash_bwd", kernel=kernel).add()


def _bwd_name(kernel: str, window) -> str:
    """A backward kernel's name in the program and the trace; the window
    kernels carry a prefix of their own."""
    return ("flash_bwd_" if window is None else "flash_win_bwd_") + kernel


def _fa_backward(
    q, k, v, delta, lse, do, s, *, causal, interpret, window=None
):
    """The backward for these shapes, chosen from the shapes alone: one kv
    block -> ``_fa_backward_fused_nk1``; several, and the sequence's f32 dq
    within ``_FUSED_BWD_DQ_VMEM`` -> ``_fa_backward_fused``; past that the
    streamed pair.  A window changes what the chosen kernels run, not the
    choice."""
    _, hq, s_pad, d = q.shape
    # Whole kv extent in one block → the single-block kernel.  An explicit
    # smaller kv-block override (sweeps/tests) asks for several blocks.
    if (_BWD_BLOCK_KV is None or _BWD_BLOCK_KV >= s_pad) and (
        s_pad <= _FUSED_BWD_MAX_KV
        or s_pad == _pick_block(s_pad, _BWD_BLOCK_KV, _BWD_BLOCK_KV_DEFAULT)
    ):
        backward = _fa_backward_fused_nk1
    elif (hq // k.shape[1]) * s_pad * _lanes(d) * 4 <= _FUSED_BWD_DQ_VMEM:
        backward = _fa_backward_fused
    else:
        backward = _fa_backward_streamed
    return backward(
        q, k, v, delta, lse, do, s, causal=causal, interpret=interpret,
        window=window,
    )


def _kv_major_specs(*, bq, bkv, nq, groups, causal, window=None, nqb=None):
    """Block specs of a grid ``(b, hkv, kv block, (gqa group, q block))``:
    ``gq_spec(width)`` for what a query row carries (q, do, lse, delta),
    its q axis clamped to the causal diagonal, and ``kv_spec(width)`` for
    k, v, dk and dv.  With a window the inner axis holds ``nqb`` q blocks
    a group, counted from the first of the kv block's band and clamped to
    its last."""
    import jax.experimental.pallas as pl

    if window is None:
        steps = nq  # the inner axis' q blocks a group
        q_block = _diag_clamp(causal, bq, bkv, jnp.maximum)
    else:
        steps = nqb

        def q_block(step, ki):
            first, last = _q_band(ki, bq=bq, bkv=bkv, window=window, nq=nq)
            return jnp.minimum(first + step, last)

    def gq_spec(width):
        return pl.BlockSpec(
            (1, 1, bq, width),
            lambda bi, hkvi, ki, idx, g=groups, n=steps: (
                bi, hkvi * g + idx // n, q_block(idx % n, ki), 0
            ),
        )

    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, bkv, width), lambda bi, hkvi, ki, idx: (bi, hkvi, ki, 0)
        )

    return gq_spec, kv_spec


def _fa_backward_fused(
    q, k, v, delta, lse, do, s, *, causal, interpret, window=None
):
    """One-kernel backward for several kv blocks (``_dqkv_stream_kernel``)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    _count_bwd("fused", window)
    b, hq, s_pad, d = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    groups = hq // hkv
    bq = _pick_block(s_pad, _BWD_BLOCK_Q, _BWD_BLOCK_Q_DEFAULT)
    bkv = _pick_block(s_pad, _BWD_BLOCK_KV, _BWD_BLOCK_KV_DEFAULT)
    nq, nk = s_pad // bq, s_pad // bkv
    band = _q_band_args(nk, nq, bq, bkv, window)
    gq_spec, kv_spec = _kv_major_specs(
        bq=bq, bkv=bkv, nq=nq, groups=groups, causal=causal, **band
    )
    dq_spec = pl.BlockSpec(
        (1, groups, s_pad, d), lambda bi, hkvi, ki, idx: (bi, hkvi, 0, 0)
    )
    # Mosaic's default scoped limit (16 MiB) is below the accumulator and
    # its output block alone at 8k, so the call states what it holds:
    # every block twice (the pipeline's two buffers), the scratch, and the
    # (bq, bkv) temporaries of ``_p_ds``: logits, p, dp, ds in f32, and p
    # and ds again in the matmul dtype.
    f32 = jnp.float32
    blocks = (
        _vmem_bytes(bq, d, q.dtype) + _vmem_bytes(bq, dv, do.dtype)
        + 2 * _vmem_bytes(bq, 1, f32)
        + 2 * _vmem_bytes(bkv, d, k.dtype) + 2 * _vmem_bytes(bkv, dv, v.dtype)
        + _vmem_bytes(groups * s_pad, d, q.dtype)
    )
    scratch = (
        _vmem_bytes(bkv, d, f32) + _vmem_bytes(bkv, dv, f32)
        + _vmem_bytes(groups * s_pad, d, f32)
    )
    temporaries = 4 * _vmem_bytes(bq, bkv, f32) + 2 * _vmem_bytes(
        bq, bkv, q.dtype
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _dqkv_stream_kernel, scale=1.0 / (d**0.5), causal=causal, bq=bq,
            bkv=bkv, s=s, s_pad=s_pad, nq=nq, **band,
        ),
        grid=(b, hkv, nk, groups * band.get("nqb", nq)),
        in_specs=[
            gq_spec(d), kv_spec(d), kv_spec(dv), gq_spec(dv), gq_spec(1),
            gq_spec(1),
        ],
        out_specs=[dq_spec, kv_spec(d), kv_spec(dv)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), f32),
            pltpu.VMEM((bkv, dv), f32),
            pltpu.VMEM((groups, s_pad, d), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            # Both inner axes carry accumulators: dq over the kv blocks,
            # dk and dv over the (group, q block) pairs.
            dimension_semantics=(
                "parallel", "parallel", "arbitrary", "arbitrary"
            ),
            vmem_limit_bytes=2 * blocks + scratch + temporaries,
        ),
        interpret=interpret,
        name=_bwd_name("fused", window),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _q_band_args(nk, nq, bq, bkv, window) -> dict:
    """What a kv-major kernel and its specs are told of a window: nothing
    without one, else the window and the q blocks a group its inner axis
    holds."""
    if window is None:
        return {}
    return {
        "window": window,
        "nqb": _q_band_steps(nk, nq, bq=bq, bkv=bkv, window=window),
    }


def _fa_backward_streamed(
    q, k, v, delta, lse, do, s, *, causal, interpret, bq=None, bkv=None,
    window=None,
):
    """The streamed two-kernel backward (dq kernel + dk/dv kernel), kv as
    a grid axis.  ``bq``/``bkv`` are normally derived from the sweep
    overrides; the fused path passes explicit VMEM-safe blocks when it
    falls back here."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    _count_bwd("pair", window)
    b, hq, s_pad, d = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    groups = hq // hkv
    if bq is None:
        bq = _pick_block(s_pad, _BWD_BLOCK_Q, _BWD_BLOCK_Q_DEFAULT)
    if bkv is None:
        bkv = _pick_block(s_pad, _BWD_BLOCK_KV, _BWD_BLOCK_KV_DEFAULT)
    nq, nk = s_pad // bq, s_pad // bkv
    scale = 1.0 / (d**0.5)

    def q_spec(width):
        return pl.BlockSpec(
            (1, 1, bq, width), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        )

    kv_clamp = _stream_kv(causal, bq, bkv, window)
    # With a window the dq kernel's streamed axis spans the band.
    kv_steps = nk if window is None else _kv_band_steps(
        nq, bq=bq, bkv=bkv, window=window
    )

    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, bkv, width),
            lambda bi, hi, qi, ki, g=groups: (
                bi, hi // g, kv_clamp(ki, qi), 0
            ),
        )


    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv, s=s,
            s_pad=s_pad, window=window,
        ),
        grid=(b, hq, nq, kv_steps),
        in_specs=[
            q_spec(d), kv_spec(d), kv_spec(dv), q_spec(dv), q_spec(1),
            q_spec(1),
        ],
        out_specs=q_spec(d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_bwd_name("dq", window),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid over kv blocks with the (group, q-block) reduction as the
    # innermost axis — the GQA head-group sum happens in the accumulator.
    band = _q_band_args(nk, nq, bq, bkv, window)
    gq_spec, kv_out_spec = _kv_major_specs(
        bq=bq, bkv=bkv, nq=nq, groups=groups, causal=causal, **band
    )

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv, s=s,
            s_pad=s_pad, nq=nq, **band,
        ),
        grid=(b, hkv, nk, groups * band.get("nqb", nq)),
        in_specs=[
            gq_spec(d), kv_out_spec(d), kv_out_spec(dv), gq_spec(dv),
            gq_spec(1), gq_spec(1),
        ],
        out_specs=[kv_out_spec(d), kv_out_spec(dv)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_bwd_name("dkv", window),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry: q, k, v in the padded kernel layout (B, H, S_pad, D),
# the result in the model's (B, S_pad, Hq*D)


# What a rematerialised block keeps of this kernel: ``_fa_fwd`` names the
# forward kernel's two results ``flash_out`` and ``flash_lse``, and
# ``jax.checkpoint(block, policy=ops.remat.REMAT_POLICY)`` saves both
# beside the block's input, so the backward pass recomputes q, k and v (the
# backward kernels read them) and never ``flash_fwd``.  Per layer that is
# B*S_pad*Hq*(D*itemsize + 4) bytes: the output in the compute dtype and
# one float32 log-sum-exp per query row.  Where the names never appear
# (jnp or ring attention, serving) the policy saves nothing.  The policy
# lives in ``ops/remat.py`` because it is every kernel's: the selective
# scan names its own two results there too.


def _rows(x):
    """Kernel layout ``(B, H, S, D)`` -> ``(B, S, H*D)``, the model's own
    layout of the attention output.  It is what leaves the VJP and what a
    remat policy saves: stacked over the layers of a scan it is lane-dense,
    where the kernel's 64-wide minor dimension is tiled out to 128."""
    b, h, s, d = x.shape
    with jax.named_scope("relayout"):
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


# The primal: runs only where nothing differentiates the call (inference,
# ``jax.eval_shape``).  Under ``jax.grad``/``jax.vjp`` JAX traces ``_fa_fwd``
# in its place, so a name given here would never reach a remat policy.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fa(q, k, v, s, causal, interpret, window):
    out, _ = _fa_forward_padded(
        q, k, v, s, causal=causal, interpret=interpret, window=window
    )
    return _rows(out)


def _fa_fwd(q, k, v, s, causal, interpret, window):
    out, lse = _fa_forward_padded(
        q, k, v, s, causal=causal, interpret=interpret, window=window
    )
    # The named ``out`` is BOTH the result and the residual: were the
    # residual another array than the one returned, the saved value and
    # the one the backward pass reads would be two variables, and the
    # recompute would keep ``flash_fwd`` alive to produce the second.
    out = checkpoint_name(_rows(out), "flash_out")
    # Without the kernel's unit minor dimension, which a stacked residual
    # would carry tiled out to 128 lanes.
    with jax.named_scope("relayout"):
        lse = lse[..., 0]
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _fa_bwd(s, causal, interpret, window, res, do):
    q, k, v, out, lse = res
    b, hq, s_pad, _ = q.shape
    d = v.shape[-1]
    do = do.reshape(b, s_pad, hq, d)
    # delta = rowsum(do * out), per query row and head: taken in the layout
    # both arrive in, so ``out`` is never carried back to the kernel's.
    with jax.named_scope("delta"):
        delta = jnp.sum(
            do.astype(jnp.float32)
            * out.reshape(b, s_pad, hq, d).astype(jnp.float32),
            axis=-1,
        ).transpose(0, 2, 1)[..., None]  # (B, Hq, S_pad, 1)
    with jax.named_scope("relayout"):
        lse, do = lse[..., None], do.transpose(0, 2, 1, 3)
    return _fa_backward(
        q, k, v, delta, lse, do, s,
        causal=causal, interpret=interpret, window=window,
    )


_fa.defvjp(_fa_fwd, _fa_bwd)


def _check_window(window, causal):
    if window is None:
        return
    if not causal:
        raise ValueError("attention: window= needs causal=True")
    if int(window) < 1:
        raise ValueError(f"attention: window must be at least 1, got {window}")


def flash_attention(
    q, k, v, *, causal: bool = True, interpret: Optional[bool] = None,
    window: Optional[int] = None,
):
    """Fused attention.  Layout matches the model stack: ``(B, S, H, D)``.
    ``v`` may have a head width of its own (``(B, S, Hkv, Dv)``, as in
    latent attention): the result is then ``(B, S, Hq, Dv)``.

    ``window=W`` (static, with ``causal``): key ``j`` is visible to query
    ``t`` iff ``0 <= t - j < W``; the kernels run the band's blocks only
    (``flash_win_*``).  A window that holds the whole sequence is plain
    causal attention and runs the plain kernels.

    Any sequence length is accepted (padded to the TPU tile grain and masked
    in-kernel).  ``interpret``: force the Pallas interpreter (None = auto:
    interpret on non-TPU backends so the kernel is testable on the CPU mesh
    rig).
    """
    _check_window(window, causal)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Counted per trace: a kernel that ran through the interpreter (and
    # not through Mosaic) must be visible to whoever reads the counters.
    _telemetry.counter(
        "attention.flash", interpret=str(bool(interpret)).lower()
    ).add()
    b, s, hq, _ = q.shape
    if window is not None:
        window = None if window >= s else int(window)
    if window is not None:
        _telemetry.counter("attention.flash_window", window=window).add()
    d = v.shape[-1]
    s_pad = _pad_len(s)
    # Kernel layout is (B, H, S, D).  ``relayout``: what the kernels' doors
    # cost, here, in ``_rows`` and around ``lse`` and ``do`` in the VJP.
    with jax.named_scope("relayout"):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        if s_pad != s:
            pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
            qt, kt, vt = (jnp.pad(t, pad) for t in (qt, kt, vt))
    out = _fa(qt, kt, vt, s, causal, interpret, window)  # (B, S_pad, Hq*D)
    with jax.named_scope("relayout"):
        return out[:, :s].reshape(b, s, hq, d)


# ---------------------------------------------------------------------------
# SPMD wrapper: the kernel under a mesh.
#
# A pallas_call is a Mosaic custom call with no SPMD partitioning rules, so
# inside a sharded jit program XLA cannot partition it (round-2's dispatcher
# therefore fell back to O(S²) jnp attention for every multi-chip train
# step).  Attention is embarrassingly parallel over batch and head, so the
# TPU-native fix is shard_map: run the kernel per-device on its local
# (batch-shard, head-shard) block — no collectives, sequence replicated —
# while dp/fsdp shard batch and tp shards heads exactly as the Megatron
# projections already laid them out (contiguous head chunks align q-head
# groups with their kv heads under GQA).


def _mesh_split(mesh, batch_axes, head_axis):
    """Nontrivial (size>1) batch axes and head axis present in ``mesh``."""
    batch = tuple(
        a for a in batch_axes if a in mesh.shape and mesh.shape[a] > 1
    )
    head = (
        head_axis
        if head_axis in mesh.shape and mesh.shape[head_axis] > 1
        else None
    )
    return batch, head


def shardable(
    mesh, q_shape, kv_shape, *,
    batch_axes=("dp", "fsdp"), head_axis="tp",
) -> bool:
    """Whether the kernel can run under ``mesh`` via :func:`flash_attention_sharded`:
    the dp/fsdp product must divide batch and tp must divide both head
    counts (whole GQA groups per shard)."""
    batch, head = _mesh_split(mesh, batch_axes, head_axis)
    b, _, hq, _ = q_shape
    hkv = kv_shape[2]
    nb = 1
    for a in batch:
        nb *= mesh.shape[a]
    tp = mesh.shape[head] if head else 1
    return b % nb == 0 and hq % tp == 0 and hkv % tp == 0


def flash_attention_sharded(
    q, k, v, *,
    causal: bool = True,
    mesh,
    batch_axes=("dp", "fsdp"),
    head_axis: str = "tp",
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
):
    """:func:`flash_attention` under a mesh: batch sharded over
    ``batch_axes``, heads over ``head_axis``, sequence replicated.

    Layout ``(B, S, H, D)`` as everywhere in the model stack.  Must not be
    called inside another shard_map over the same axes (the pipeline stage
    body) — the dispatcher routes those to jnp attention.
    """
    from jax.sharding import PartitionSpec as P

    if not shardable(
        mesh, q.shape, k.shape, batch_axes=batch_axes, head_axis=head_axis
    ):
        raise ValueError(
            f"flash_attention_sharded: q {q.shape} / kv {k.shape} not "
            f"divisible over mesh {dict(mesh.shape)} "
            f"(batch_axes={batch_axes}, head_axis={head_axis!r})"
        )
    batch, head = _mesh_split(mesh, batch_axes, head_axis)
    if not batch and head is None:
        return flash_attention(
            q, k, v, causal=causal, interpret=interpret, window=window
        )
    spec = P(batch if batch else None, None, head, None)

    def local(ql, kl, vl):
        return flash_attention(
            ql, kl, vl, causal=causal, interpret=interpret, window=window
        )

    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
