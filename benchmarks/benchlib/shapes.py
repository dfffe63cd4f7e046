"""From shapes to the operations and bytes the algorithm needs.

The per-family counts (parameters inside matrix multiplications, bytes a
decode step reads) live with each family adapter in ``families/``; the
formulas that combine them are here.
"""


def train_flops_per_token(matmul_params: int, n_layers: int, d_attn: int,
                          seq: int) -> float:
    """Forward + backward FLOPs one token requires, nothing recomputed.

    Matrix multiplications: 2 FLOPs per parameter forward, twice that
    backward: ``6 * matmul_params``.  Causal attention: a query at position
    ``p`` meets ``p + 1`` keys, ``(seq + 1) / 2`` on average; QK^T and AV
    each cost ``2 * d_attn`` per key, so ``2 * d_attn * (seq + 1)`` forward
    per layer and three times that with the backward.  Masked-out products
    are NOT counted: a kernel that computes them anyway gains nothing."""
    return 6.0 * matmul_params + 6.0 * d_attn * (seq + 1) * n_layers


def decode_bytes_per_step(weight_bytes: int, kv_bytes_per_position: int,
                          live_positions: float) -> float:
    """Bytes one decode step must read: every weight once, plus the cached
    keys and values of every live position of every running slot."""
    return weight_bytes + kv_bytes_per_position * live_positions
