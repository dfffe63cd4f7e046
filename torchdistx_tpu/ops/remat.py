"""What a rematerialised block keeps of the kernels it ran.

``jax.checkpoint(block, policy=REMAT_POLICY)`` saves, beside the block's
arguments, the forward results that the kernels' VJP rules name, so the
backward pass recomputes the cheap work around a kernel (norms,
projections, the feed-forward) and goes straight to the backward kernel:

================  ====================================  =====================
name              array, per layer and device           named in
================  ====================================  =====================
``flash_out``     attention output ``(B, S, Hq*D)``,    ``flash_attention.
                  the compute dtype                     _fa_fwd``
``flash_lse``     log-sum-exp ``(B, Hq, S)`` float32    the same
``ssm_out``       the scan's ``y`` ``(B, T, C)``, the   ``selective_scan.
                  model's dtype                         _scan_fwd``
``ssm_starts``    chunk-start states ``(B, T/chunk,     the same
                  N, C)`` float32
``moe_selected``  the experts each token chose          ``routed_experts.
                  ``(T, k)`` int32: the kept products   route``
                  are rows of the order THIS choice
                  sorts, so the replay sorts by it
``moe_gate``      the first row chunk's gate product    ``routed_experts.
                  ``(R, F)``, before the unit's         _forward``
                  activation (silu or relu), the
                  compute dtype
``moe_up``        its up product ``(R, F)``             the same
``moe_out``       the routed layer's result ``(T, D)``  ``routed_experts.
                  in the model's dtype, stored only     routed_experts``
                  where a backward reads it (a norm
                  after the layer)
================  ====================================  =====================

Bytes a layer: attention ``B*S*Hq*(D*itemsize + 4)``; a state-space mixer
``B*T*C*(itemsize + 4*N/chunk)`` — at 4,096 x 5,120 channels, 16 states,
chunks of 128 in bfloat16: 41.9 MB + 10.5 MB = 52.4 MB, for which
``ssm_scan_fwd`` runs once a layer instead of twice.

A routed-expert layer ``2*R*F*itemsize`` (``R`` the rows of a chunk,
``routed_experts._row_bound``) + ``4*T*k``, and ``T*D*itemsize`` more where
the result is read: at 8,192 tokens, 6 of 128 experts 768 wide, a quarter held (``R``
16,384) 50.3 MB with no result stored (the block ends ``x + out``); at 8
of 128 experts 1,024 wide (``R`` 22,016) 90.2 MB + 33.6 MB, for which the
layer's first chunk runs 9 grouped products, not 12 and 15: none of the
forward's three runs again, in the remat's replay or in the backward; at
16,384 tokens, 6 of 64 experts 768 wide, a quarter held (``R`` 32,768)
100.7 MB, no result stored.
The four names go together: a policy that keeps the products and lets the
replay choose again pairs them with another order's rows wherever a near
tie flips (bfloat16 scores round otherwise in the replay's program).

The banded flash kernels of a window call (``flash_win_*``) name the same
two arrays, so a window layer's forward kernel runs once a layer too.

ONE policy for every family: ``save_only_these_names`` saves a name only
where the kernel that gives it was traced, so a block without a scan (or
without routed experts, or with ``jnp``/ring attention, or in serving)
saves nothing for it and lowers to the program a plain ``jax.checkpoint``
gives.
"""

import jax

__all__ = ["REMAT_POLICY"]

REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse", "ssm_out", "ssm_starts",
    "moe_selected", "moe_gate", "moe_up", "moe_out",
)
