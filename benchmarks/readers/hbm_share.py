"""A decode program's share of the HBM roofline: the bytes one decode step
MUST read (every weight once, the cached keys and values of every live
position; from shapes) over the device time the trace gives one step of the
named program, over the chip's peak bandwidth."""

from benchlib import peaks, shapes


def read(run, program):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    prog = (trace or {}).get("programs", {}).get(program)
    live = counts.get("live_positions_traced")
    if not prog or not prog["count"] or live is None:
        return None
    step_s = prog["device_s"] / (prog["count"] * counts["decode_chunk"])
    need = shapes.decode_bytes_per_step(
        cell.counts["decode_read_params"] * cell.itemsize,
        cell.counts["kv_per_position"] * cell.itemsize, live,
    )
    bw = peaks.peaks(run["peaks_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / step_s / bw
