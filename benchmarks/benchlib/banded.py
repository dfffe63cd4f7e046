"""Operations the algorithm needs, from shapes, for causal attention
through a window: key ``j`` is visible to query ``t`` iff ``0 <= t - j <
window``.  Forward + backward, nothing recomputed, pairs outside the band
not counted: a kernel that computes them anyway gains nothing."""


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a sequence and head: a query at position ``t``
    meets ``min(t + 1, window)`` keys."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def window_attention_flops(rows: int, seq: int, window: int, n_heads: int,
                           d_qk: int, d_v: int, n_layers: int) -> float:
    """Forward, a pair costs ``2 * d_qk`` in QK^T and ``2 * d_v`` in PV;
    the backward twice that (dQ and dK at ``d_qk``, dP and dV at ``d_v``)."""
    pairs = rows * n_heads * window_pairs(seq, window)
    return 3.0 * 2.0 * (d_qk + d_v) * pairs * n_layers
