"""Bytes the selective scans of a step MUST move, from shapes alone and the
same whatever the kernels fuse or keep: the scan proper, forward +
backward, nothing recomputed.

Forward, a layer reads ``u`` and ``delta`` (``T x d_inner`` each), ``B``
and ``C`` (``T x N`` each), ``A`` (``d_inner x N``, float32) and ``D``
(``d_inner``), and writes ``y``.  Backward it reads those and ``dy`` and
writes ``du``, ``ddelta``, ``dB``, ``dC``, ``dA``, ``dD``.  Activations
at the model's itemsize.  NOT counted, because they are the
implementation's: the gate ``z``, the softplus, the chunk-start states, the
forward the block's remat runs again, the padding of ``B`` and ``C``."""


def selective_scan_bytes(rows: int, seq: int, d_inner: int, d_state: int,
                         n_layers: int, itemsize: int) -> float:
    wide = rows * seq * d_inner * itemsize  # u, delta, y, dy, du, ddelta
    narrow = rows * seq * d_state * itemsize  # B, C, dB, dC
    fixed = d_inner * d_state * 4 + d_inner * itemsize  # A, D (and dA, dD)
    forward = 3 * wide + 2 * narrow + fixed
    backward = 5 * wide + 4 * narrow + 2 * fixed
    return float(n_layers * (forward + backward))
