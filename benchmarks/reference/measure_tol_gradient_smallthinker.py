#!/usr/bin/env python3
"""The readings on either side of ``tol.gradient`` and
``tol.gradient_rows`` of the SmallThinker training cell
(``drivers/train_steps_routed_queued``, family ``smallthinker``); not run
by the driver.  ``measure_tol_gradient_banded``'s program (its ``main``,
its controls' loop, its output file) with this family's faults:

    python3 benchmarks/reference/measure_tol_gradient_smallthinker.py --workload <cell> [--seeds 1]
    python3 benchmarks/reference/measure_tol_gradient_smallthinker.py --workload <cell> --fault <name> [--seed n]

Without ``--fault``: the plain reference's gradient on the cell's shapes,
float32 ``highest``, against the SAME reference computed worse (``bf16``:
bfloat16 at default precision, what rounding alone does), each through the
driver's own comparison.  With ``--fault``: one run of the whole harness
with that fault put into the PROGRAM, which has to print ``"correct":
false``.  The faults, each of which has to come out NOT correct:

* ``fp8_experts``: bfloat16, the operands of the expert products rounded
  to float8 e4m3's three mantissa bits;
* ``skip_expert``: the routed sum leaves out one held expert;
* ``silu_unit``: ``silu`` in the experts' gated unit, where ``relu`` belongs;
* ``router_post_norm``: the router fed the post-attention norm's output,
  the experts' own input, and not the layer's;
* ``rope_full``: rope on the full layers too;
* ``window_causal``: the window layers attend the whole triangle;
* ``edge_block``: the band's lower edge unmasked: every key of the
  kernels' edge block is visible (blocks of ``BLOCK`` positions).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from reference import measure_tol_gradient as shared  # noqa: E402
from reference import measure_tol_gradient_banded as banded  # noqa: E402

# ``main`` below hands ``banded.main`` this file's faults in place of its own.
_below_the_family = banded.fault_the_program

FAULTS = (
    "fp8_experts", "skip_expert", "silu_unit", "router_post_norm",
    "rope_full", "window_causal", "edge_block",
)


def fault_the_reference(ref, name: str, sizes):
    """Put ``name`` into the reference module; returns the undo."""
    saved = {
        k: getattr(ref, k) for k in ("_visible", "_attn", "_reglu", "routed")
    }

    def undo():
        for k, v in saved.items():
            setattr(ref, k, v)

    visible, attn, reglu, routed = (
        ref._visible, ref._attn, ref._reglu, ref.routed
    )
    if name == "fp8_experts":
        import jax

        fp8 = shared.fp8

        def reglu8(u, gate, up, down):
            u, gate, up, down = map(fp8, (u, gate, up, down))
            return fp8(jax.nn.relu(u @ gate) * (u @ up)) @ down

        ref._reglu = reglu8
    elif name == "skip_expert":
        ref.routed = lambda x, u, lp, sizes: routed(
            x, u, dict(lp, e_down=shared.without_expert_0(lp["e_down"])), sizes
        )
    elif name == "silu_unit":
        import jax

        ref._reglu = lambda u, gate, up, down: (
            jax.nn.silu(u @ gate) * (u @ up)
        ) @ down
    elif name == "router_post_norm":
        ref.routed = lambda x, u, lp, sizes: routed(u, u, lp, sizes)
    elif name == "rope_full":
        ref._attn = lambda x, lp, sizes, slides, ropes: attn(
            x, lp, sizes, slides, True
        )
    elif name == "window_causal":
        ref._visible = lambda rows, cols, window: visible(rows, cols, None)
    elif name == "edge_block":
        def edge_visible(rows, cols, window):
            plain = visible(rows, cols, None)
            if window is None:
                return plain
            block = banded.BLOCK
            first = (rows // block * block - window + 1) // block * block
            return plain & (cols[None, :] >= first[:, None])

        ref._visible = edge_visible
    return undo


def fault_the_program(name: str, sizes) -> None:
    from torchdistx_tpu.models import smallthinker as family

    routed, attn = family.routed_experts, family._attn
    if name == "skip_expert":
        family.routed_experts = lambda h, r, g, u, d, **kw: routed(
            h, r, g, u, shared.without_expert_0(d), **kw
        )
    elif name == "silu_unit":
        family.routed_experts = lambda *a, unit=None, **kw: routed(
            *a, unit="silu", **kw
        )
    elif name == "router_post_norm":
        family.routed_experts = lambda *a, routing=None, **kw: routed(*a, **kw)
    elif name == "rope_full":
        import dataclasses

        family._attn = lambda x, lp, cfg, kind, **kw: attn(
            x, lp,
            cfg if kind == family.WINDOW
            else dataclasses.replace(cfg, window=1 << 30),
            family.WINDOW, **kw
        )
    elif name == "window_causal":
        attention = family.attention
        family.attention = lambda *a, window=None, **kw: attention(*a, **kw)
    else:  # fp8_experts and edge_block live below the family
        _below_the_family(name, sizes)


def main() -> int:
    banded.FAULTS = FAULTS
    banded.fault_the_reference = fault_the_reference
    banded.fault_the_program = fault_the_program
    return banded.main()


if __name__ == "__main__":
    sys.exit(main())
