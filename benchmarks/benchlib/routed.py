"""Operations the algorithm needs, from shapes, for the two kernels of a
latent-attention, routed-expert model: causal attention with a value
width of its own, and the grouped expert products.  Forward + backward,
nothing recomputed, masked-out products not counted."""


def causal_attention_flops(rows: int, seq: int, n_heads: int, d_qk: int,
                           d_v: int, n_layers: int) -> float:
    """A query at position ``p`` meets ``p + 1`` keys: ``seq * (seq + 1) /
    2`` pairs a sequence and head.  Forward, a pair costs ``2 * d_qk`` in
    QK^T and ``2 * d_v`` in PV; the backward twice that (dQ and dK at
    ``d_qk``, dP and dV at ``d_v``)."""
    pairs = rows * n_heads * seq * (seq + 1) / 2
    return 3.0 * 2.0 * (d_qk + d_v) * pairs * n_layers


def expert_flops(assignments: float, expert_params: int) -> float:
    """``assignments``: (token, choice) pairs a step routed to experts held
    here, over all expert layers.  Each multiplies one expert's three
    matrices: 2 FLOPs a parameter forward, twice that backward."""
    return 6.0 * expert_params * assignments


def kernel_seconds_per_step(trace: dict, ops, program: str, prefixes):
    """Device seconds one execution of ``program`` spends in operations
    whose instruction name starts with one of ``prefixes``.  ``ops`` are
    ``[program/instruction shape, seconds]`` rows clipped to the traced
    window, which also holds the ends of executions cut by its edges: the
    window's executions are counted as its busy time over the device time
    of a whole one.  None when the program or the operations are not in
    the trace."""
    prog = (trace.get("programs") or {}).get(program)
    if not prog or not prog["count"] or not trace.get("busy_s"):
        return None
    seconds = sum(
        s for key, s in ops
        if key.startswith(program + "/")
        and key[len(program) + 1:].startswith(tuple(prefixes))
    )
    if not seconds:
        return None
    executions = trace["busy_s"] / (prog["device_s"] / prog["count"])
    return seconds / executions
