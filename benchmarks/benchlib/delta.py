"""Bytes the gated delta rule needs, from shapes, for Olmo-Hybrid's
``linear_attention`` layers: what any implementation must move, forward +
backward, nothing recomputed; the implementation's own arrays (chunk-start
states, padding, relayouts) not counted."""


def delta_rule_bytes(rows: int, seq: int, heads: int, d_k: int, d_v: int,
                     layers: int, itemsize: int) -> float:
    """Forward reads ``q``, ``k``, ``v``, ``g``, ``beta`` and writes ``o``;
    backward reads those and ``do`` and writes ``dq``, ``dk``, ``dv``,
    ``dg``, ``dbeta``.  ``q``, ``k`` and their gradients are ``(T, H *
    d_k)``, ``v``, ``o`` and theirs ``(T, H * d_v)`` at ``itemsize``; the
    gates and theirs ``(T, H)`` float32."""
    per_position = heads * (itemsize * (6 * d_k + 5 * d_v) + 4 * 6)
    return float(rows * seq * per_position * layers)
