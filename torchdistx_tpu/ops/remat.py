"""What a rematerialised block keeps of the kernels it ran.

``jax.checkpoint(block, policy=REMAT_POLICY)`` saves, beside the block's
arguments, the forward results that the kernels' VJP rules name, so the
backward pass recomputes the cheap work around a kernel (norms,
projections, the feed-forward) and goes straight to the backward kernel:

==============  ====================================  =====================
name            array, per layer and device           named in
==============  ====================================  =====================
``flash_out``   attention output ``(B, S, Hq*D)``,    ``flash_attention.
                the compute dtype                     _fa_fwd``
``flash_lse``   log-sum-exp ``(B, Hq, S)`` float32    the same
``ssm_out``     the scan's ``y`` ``(B, T, C)``, the   ``selective_scan.
                model's dtype                         _scan_fwd``
``ssm_starts``  chunk-start states ``(B, T/chunk,     the same
                N, C)`` float32
==============  ====================================  =====================

Bytes a layer: attention ``B*S*Hq*(D*itemsize + 4)``; a state-space mixer
``B*T*C*(itemsize + 4*N/chunk)`` — at 4,096 x 5,120 channels, 16 states,
chunks of 128 in bfloat16: 41.9 MB + 10.5 MB = 52.4 MB, for which
``ssm_scan_fwd`` runs once a layer instead of twice.

The banded flash kernels of a window call (``flash_win_*``) name the same
two arrays, so a window layer's forward kernel runs once a layer too.

ONE policy for every family: ``save_only_these_names`` saves a name only
where the kernel that gives it was traced, so a block without a scan (or
with ``jnp``/ring attention, or in serving) saves nothing for it and
lowers to the program a plain ``jax.checkpoint`` gives.
"""

import jax

__all__ = ["REMAT_POLICY"]

REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse", "ssm_out", "ssm_starts"
)
