"""Operations and bytes the algorithm needs, from shapes, for MiniCPM-SALA's
two mixers: block-sparse attention over the blocks each token chose, and
decayed linear (lightning) attention.  Forward + backward, nothing
recomputed, masked products not counted, the implementation's own arrays
(chosen bits, chunk-start states, padding) not moved."""


def chosen_pairs(seq: int, block: int, topk: int, dense_len: int) -> int:
    """(query, key) pairs a sequence and head: a token at ``t`` sees ``t + 1``
    keys while it has at most ``topk`` blocks (or the layer runs dense, at
    ``seq <= dense_len``), else ``topk - 1`` whole blocks and its own block
    up to itself, ``(topk - 1) * block + t % block + 1``."""
    if seq <= dense_len:
        return seq * (seq + 1) // 2
    return sum(
        t + 1 if t // block + 1 <= topk else (topk - 1) * block + t % block + 1
        for t in range(seq)
    )


def chunk_pairs(seq: int, chunk: int) -> int:
    """Pairs the quadratic form inside the chunks holds, ``j <= i`` within
    a chunk, a sequence and head."""
    whole, rest = divmod(seq, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def sparse_attention_flops(rows: int, seq: int, block: int, topk: int,
                           dense_len: int, n_heads: int, d_qk: int, d_v: int,
                           n_layers: int) -> float:
    """Forward, a pair costs ``2 * d_qk`` in QK^T and ``2 * d_v`` in PV; the
    backward twice that (dQ and dK at ``d_qk``, dP and dV at ``d_v``)."""
    pairs = rows * n_heads * chosen_pairs(seq, block, topk, dense_len)
    return 3.0 * 2.0 * (d_qk + d_v) * pairs * n_layers


def lightning_bytes(rows: int, seq: int, n_heads: int, head_dim: int,
                    n_layers: int, itemsize: int) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, do and
    writes dq, dk, dv: eleven ``(T, H * D)`` arrays a layer."""
    return float(11 * rows * seq * n_heads * head_dim * itemsize * n_layers)
