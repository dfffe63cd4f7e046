"""Pipeline parallelism: GPipe microbatch schedule over a ``pp`` mesh axis.

The stacked-layer representation the model families already use
(``(n_layers, ...)`` leaves scanned by ``lax.scan``) extends naturally to
pipeline parallelism: shard the layer dim over ``pp`` so each device holds a
contiguous *stage* of ``n_layers / pp`` blocks, split the batch into
microbatches, and run the classic GPipe schedule — at tick ``t`` stage ``p``
processes microbatch ``t - p``, handing activations to stage ``p+1`` with a
single neighbor ``ppermute`` hop (ICI).  ``M + P - 1`` ticks drain the
pipeline; bubble fraction ``(P-1)/(M+P-1)`` shrinks with more microbatches.

Implemented as ``shard_map`` + ``lax.scan`` over ticks: nests inside the
jitted train step, composes with dp/fsdp/tp on the other mesh axes, and is
reverse-differentiable (scan + ppermute transpose), so pipeline-parallel
*training* works through plain ``jax.grad``.

Cost model (per device, ``P`` stages, ``M`` microbatches, ``T`` = one
stage's per-microbatch compute):

* **Ticks**: ``M + P - 1``; wall-clock ``(M + P - 1) · T`` against a
  perfectly overlapped ideal of ``M · T`` → bubble overhead
  ``(P - 1)/M``, amortized away by raising ``n_microbatches``.
* **FLOPs**: stage compute is gated behind ``lax.cond`` on tick validity
  (``0 ≤ t − p < M``), so ramp-up/drain ticks execute the identity branch —
  each device performs exactly ``M`` stage-computations of real work, the
  same FLOP count as an unpipelined run, in both the forward and the
  ``cond``-transposed backward pass.  (An earlier revision ran every stage
  on every tick: ``(P−1)/M`` pure waste.)
* **Activation memory**: inputs are replicated over the ``pp`` axis (every
  stage re-slices its current microbatch locally — no gather from stage 0),
  which costs ``B·…`` per device *once*; they remain sharded as usual over
  the automatic dp/fsdp axes, so the replication factor applies only to the
  per-dp-shard slice.  Weights are never replicated: each stage holds its
  ``L/P`` layers (sharded further by tp/fsdp on trailing dims).

The reference framework has no pipeline parallelism (SURVEY.md §2.3) — this
is native new capability, like ring attention.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward", "pipeline_value_and_grad", "stage_specs"]


def stage_specs(layer_specs, *, pp: str = "pp"):
    """Prefix every stacked-layer spec with the ``pp`` axis on the layer dim
    (composes with tp/fsdp on the trailing dims)."""
    return jax.tree.map(
        lambda s: P(pp, *s), layer_specs, is_leaf=lambda x: isinstance(x, P)
    )


def pipeline_forward(
    x,
    layer_params,
    block_fn: Callable,
    *,
    mesh,
    axis: str = "pp",
    n_microbatches: int,
):
    """Run stacked layers over ``x`` with a GPipe schedule.

    ``x``: an activation array ``(B, ...)`` or a *pytree* of them (every
    leaf with the same leading batch dim) — side channels like an MoE
    router aux-loss accumulator travel through the pipeline alongside the
    hidden state.  ``layer_params``: pytree with leading layer dim on every
    leaf, sharded ``P(axis, ...)`` (see :func:`stage_specs`).
    ``block_fn(x, lp) -> x`` is one transformer block given one layer's
    (unstacked) params, preserving the pytree structure of ``x``.
    ``n_microbatches`` must divide the global batch ``B``.

    Only the ``axis`` dimension is manual inside the ``shard_map`` — every
    other mesh axis (dp/fsdp/tp) stays *automatic*, so activations keep
    their batch sharding and stage weights keep their fsdp/tp sharding with
    XLA inserting the usual Megatron/ZeRO collectives inside each stage (no
    all-gather of stage weights, no duplicated matmuls).
    """
    names = set(mesh.axis_names)
    if axis not in names:
        raise ValueError(f"mesh has no axis {axis!r} (axes: {mesh.axis_names})")
    n_stages = mesh.shape[axis]
    leaves = jax.tree.leaves(x)
    batch = leaves[0].shape[0]
    if any(l.shape[0] != batch for l in leaves):
        raise ValueError("all activation leaves must share the batch dim")
    if batch % n_microbatches:
        raise ValueError(
            f"batch {batch} not divisible by {n_microbatches} microbatches"
        )
    x_spec = jax.tree.map(lambda l: P(*([None] * l.ndim)), x)
    param_specs_local = jax.tree.map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), layer_params
    )

    def body(x_local, params_local):
        # x_local leaves: (B_local, ...); params_local: (L/P, ...) stage.
        p = jax.lax.axis_index(axis)
        bt = batch // n_microbatches
        micro = jax.tree.map(
            lambda l: l.reshape((n_microbatches, bt) + l.shape[1:]), x_local
        )

        def run_stage(act):
            def scan_block(h, lp):
                return block_fn(h, lp), None

            with jax.named_scope("stack"):  # the scan's own work
                out, _ = jax.lax.scan(scan_block, act, params_local)
            return out

        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        n_ticks = n_microbatches + n_stages - 1
        out0 = jax.tree.map(jnp.zeros_like, micro)
        carry0 = jax.tree.map(lambda l: jnp.zeros_like(l[0]), micro)

        def tick(carry, t):
            incoming, outputs = carry
            m = t - p  # microbatch this stage works on at tick t
            valid = (m >= 0) & (m < n_microbatches)
            m_idx = jnp.clip(m, 0, n_microbatches - 1)
            stage_in = jax.tree.map(
                lambda mic, inc: jnp.where(
                    p == 0,
                    jax.lax.dynamic_index_in_dim(mic, m_idx, 0,
                                                 keepdims=False),
                    inc,
                ),
                micro, incoming,
            )
            # Gate the stage behind the validity predicate: ramp-up/drain
            # ticks take the identity branch, skipping the stage's FLOPs in
            # both the forward and (via cond's transpose) the backward pass.
            # Deadlock-freedom invariant: the predicate varies only over the
            # pp axis (it derives from this stage's axis_index and the tick),
            # so every member of any tp/fsdp collective group XLA forms
            # *inside* run_stage takes the same branch, and the pp-wide
            # ppermute below runs unconditionally every tick.  A collective
            # whose group spans pp must never move inside a branch.
            y = jax.lax.cond(valid, run_stage, lambda act: act, stage_in)
            # Last stage banks its (valid) result.
            outputs = jax.tree.map(
                lambda out, yl: jax.lax.dynamic_update_index_in_dim(
                    out,
                    out[m_idx]
                    + jnp.where(
                        valid & (p == n_stages - 1), yl, 0.0
                    ).astype(out.dtype),
                    m_idx,
                    0,
                ),
                outputs, y,
            )
            # Hand activations to the next stage.
            incoming = jax.tree.map(
                lambda yl: jax.lax.ppermute(yl, axis, perm), y
            )
            return (incoming, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (carry0, out0), jnp.arange(n_ticks)
        )
        # Only the last stage holds real outputs; make them visible on all
        # stages (they're zeros elsewhere, so a psum is a broadcast).
        outputs = jax.lax.psum(outputs, axis)
        return jax.tree.map(
            lambda out, l: out.reshape(l.shape), outputs, x_local
        )

    return jax.shard_map(
        body, mesh=mesh, in_specs=(x_spec, param_specs_local),
        out_specs=x_spec, axis_names=frozenset({axis}), check_vma=False,
    )(x, layer_params)


# ---------------------------------------------------------------------------
# 1F1B: interleaved forward/backward schedule with O(P) live activations.
#
# GPipe above relies on jax autodiff of the tick scan, which saves one
# stage-input activation per tick — O(M + P) microbatch activations live at
# the forward/backward boundary (plus per-layer residuals unless the block
# is rematerialized).  The classic fix is 1F1B (PipeDream-flush /
# Megatron-LM): a stage starts microbatch m's backward as soon as its
# gradient arrives, so at most ~P microbatches are ever in flight per stage.
#
# JAX's autodiff cannot express that interleaving (the transpose of a scan
# runs strictly after the whole forward), so :func:`pipeline_value_and_grad`
# writes the backward BY HAND inside the same tick scan: each tick a stage
# may run one forward (activation stashed in a ring buffer) and one
# backward (``jax.vjp`` re-runs the stage forward from the stashed input —
# full rematerialization — then transposes it), accumulating parameter
# gradients in the scan carry.  The loss head runs inside the LAST stage,
# per microbatch, which is what lets gradients start flowing while later
# microbatches are still going forward.
#
# Schedule (0-indexed stage p of P, microbatch m of M, one fwd slot + one
# bwd slot per tick):
#
#   fwd(m, p) = max(m + p,  2m + 2p - P + 1)     # GPipe ramp, then 1-in-2
#   bwd(m, p) = 2P - 2 - p + 2m                  # drains one stage per tick
#   ticks     = bwd(M-1, 0) + 1 = 2M + 2P - 3
#
# Steady state alternates fwd (cost T) and bwd (recompute+transpose, ~3T)
# ticks per stage, with the phases offset across stages such that every
# stage performs 4T of work per 2 ticks — the same wall-clock as GPipe with
# rematerialized blocks, at a fraction of the activation memory.
#
# Liveness: a microbatch is live on stage p from fwd(m, p) to bwd(m, p);
# the in-flight count is bounded by (3P - 3p - 2)/2, so a ring buffer of
# ``3P//2 + 1`` slots (indexed m mod slots) never collides:
# write(m + slots) > bwd(m) for every stage.  That bound — O(P), not
# O(M + P) — is the entire point; ``last_stash_slots`` exposes it to tests.

last_stash_slots = 0  # introspection: ring-buffer depth of the last trace
last_n_ticks = 0
last_grad_acc_shapes = ()  # (name, shape, dtype) of the last trace's grad accumulators


def pipeline_value_and_grad(
    embed_params,
    layer_params,
    head_params,
    tokens,
    targets,
    embed_fn: Callable,
    block_fn: Callable,
    head_loss_fn: Callable,
    *,
    mesh,
    axis: str = "pp",
    n_microbatches: int,
    shared_params=None,
):
    """Compute ``(loss, (g_embed, g_layers, g_head))`` with a 1F1B schedule.

    ``embed_fn(embed_params, tokens_mb) -> h`` runs on stage 0 per
    microbatch; ``block_fn(h, lp) -> h`` is one transformer block (scanned
    over the stage's ``L/P`` layers); ``head_loss_fn(head_params, h,
    targets_mb) -> scalar`` runs on the last stage per microbatch (mean
    over the microbatch's tokens).  ``tokens``/``targets``: ``(B, S)`` with
    ``B % n_microbatches == 0``.  The activation ``h`` may be a PYTREE —
    side channels (an MoE router aux-loss accumulator) ride the pipeline
    in every buffer (stash, hops) alongside the hidden state, exactly as
    in :func:`pipeline_forward`.

    ``shared_params``: parameters used by BOTH the embedding and the head
    (GPT-2's tied token embedding).  When given, ``embed_fn(ep, tokens_mb,
    sp)`` and ``head_loss_fn(hp, h, targets_mb, sp)`` receive it as a
    trailing argument, it is carried with ONE f32 gradient accumulator,
    and the return becomes ``(loss, (g_embed, g_layers, g_head,
    g_shared))`` — duplicating a tied (V, D) tensor into both ep and hp
    would instead cost two vocab-sized accumulators and psums per stage.

    Gradients are accumulated across microbatches in float32 and cast back
    to the parameter dtypes; the loss is the mean over microbatches.  Only
    the ``axis`` dimension is manual — dp/fsdp/tp stay automatic exactly as
    in :func:`pipeline_forward`, with the same deadlock-freedom invariant
    (every branch predicate varies only over the pp axis).
    """
    global last_stash_slots, last_n_ticks
    if axis not in set(mesh.axis_names):
        raise ValueError(f"mesh has no axis {axis!r} (axes: {mesh.axis_names})")
    n_stages = mesh.shape[axis]
    M = n_microbatches
    B, S = tokens.shape
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    bt = B // M
    n_slots = (3 * n_stages) // 2 + 1
    n_ticks = 2 * M + 2 * n_stages - 3
    last_stash_slots, last_n_ticks = n_slots, n_ticks

    def stage_fn(lp, h):
        with jax.named_scope("stack"):  # the scan's own work
            out, _ = jax.lax.scan(lambda c, l: (block_fn(c, l), None), h, lp)
        return out

    f32 = jnp.float32

    # Normalize the optional shared-params channel: internally the embed
    # and head always take a trailing ``sp`` (empty dict when unused).
    has_shared = shared_params is not None
    sp_in = shared_params if has_shared else {}

    def embed(ep_, tok_, sp_):
        return embed_fn(ep_, tok_, sp_) if has_shared else embed_fn(ep_, tok_)

    def head(hp_, y_, tgt_, sp_):
        return (
            head_loss_fn(hp_, y_, tgt_, sp_)
            if has_shared
            else head_loss_fn(hp_, y_, tgt_)
        )

    def body(ep, lp, hp, sp, tokens, targets):
        tmap = jax.tree.map
        p = jax.lax.axis_index(axis)
        up = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        down = [(i, (i - 1) % n_stages) for i in range(n_stages)]
        tok_mb = tokens.reshape(M, bt, S)
        tgt_mb = targets.reshape(M, bt, S)
        # Activation pytree structure/shapes (side channels included).
        h_ab = jax.eval_shape(
            embed, ep, jax.ShapeDtypeStruct((bt, S), tokens.dtype), sp
        )

        def zeros_h():
            return tmap(lambda a: jnp.zeros(a.shape, a.dtype), h_ab)

        def stash_read(stash, slot):
            return tmap(
                lambda st: jax.lax.dynamic_index_in_dim(
                    st, slot, 0, keepdims=False
                ),
                stash,
            )

        def stash_write(stash, slot, val):
            return tmap(
                lambda st, v: jax.lax.dynamic_update_index_in_dim(
                    st, v, slot, 0
                ),
                stash,
                val,
            )

        def zeros_f32_like(tree):
            return tmap(lambda l: jnp.zeros(l.shape, f32), tree)

        carry0 = dict(
            fc=jnp.zeros((), jnp.int32),
            bc=jnp.zeros((), jnp.int32),
            stash=tmap(
                lambda a: jnp.zeros((n_slots,) + a.shape, a.dtype), h_ab
            ),
            inc_y=zeros_h(),
            inc_m=jnp.full((), -1, jnp.int32),
            inc_g=zeros_h(),
            g_ep=zeros_f32_like(ep),
            g_lp=zeros_f32_like(lp),
            g_hp=zeros_f32_like(hp),
            g_sp=zeros_f32_like(sp),
            loss=jnp.zeros((), f32),
        )
        # Introspection for tests: the per-stage f32 gradient accumulators
        # carried through the scan (proves e.g. a tied embedding is carried
        # ONCE via shared_params, not duplicated into ep and hp).
        global last_grad_acc_shapes
        last_grad_acc_shapes = tuple(
            (name, tuple(leaf.shape), str(leaf.dtype))
            for name in ("g_ep", "g_lp", "g_hp", "g_sp")
            for leaf in jax.tree.leaves(carry0[name])
        )

        def tick(carry, t):
            # 1. Ingest the forward activation sent last tick (stages > 0).
            slot_in = jnp.maximum(carry["inc_m"], 0) % n_slots
            take = (carry["inc_m"] >= 0) & (p > 0)
            cur = stash_read(carry["stash"], slot_in)
            stash = stash_write(
                carry["stash"],
                slot_in,
                tmap(
                    lambda y, c: jnp.where(take, y, c), carry["inc_y"], cur
                ),
            )

            fc, bc = carry["fc"], carry["bc"]
            do_fwd = (
                t == jnp.maximum(fc + p, 2 * fc + 2 * p - n_stages + 1)
            ) & (fc < M)
            do_bwd = (t == 2 * n_stages - 2 - p + 2 * bc) & (bc < M)

            # 2. Forward slot.  Stage 0 embeds its microbatch and stashes
            # it; later stages read the stash.  The LAST stage never runs a
            # separate forward — its backward slot recomputes the stage via
            # vjp and feeds the head in one go.
            fi = jnp.minimum(fc, M - 1)

            def fwd_slot(stash):
                h_in = jax.lax.cond(
                    p == 0,
                    lambda: embed(
                        ep,
                        jax.lax.dynamic_index_in_dim(
                            tok_mb, fi, 0, keepdims=False
                        ),
                        sp,
                    ),
                    lambda: stash_read(stash, fi % n_slots),
                )
                stash = jax.lax.cond(
                    p == 0,
                    lambda s: stash_write(s, fi % n_slots, h_in),
                    lambda s: s,
                    stash,
                )
                y = jax.lax.cond(
                    p == n_stages - 1,
                    zeros_h,
                    lambda: stage_fn(lp, h_in),
                )
                return stash, y

            stash, y_out = jax.lax.cond(
                do_fwd,
                fwd_slot,
                lambda s: (s, zeros_h()),
                stash,
            )
            m_out = jnp.where(do_fwd & (p < n_stages - 1), fc, -1)

            # 3. Backward slot.  Recompute the stage forward from the
            # stashed input (full remat), transpose it with the cotangent —
            # the incoming pipeline gradient, or, on the last stage, the
            # head loss gradient computed right here.
            bi = jnp.minimum(bc, M - 1)

            def bwd_slot():
                h_in = stash_read(stash, bi % n_slots)
                y, vjp = jax.vjp(stage_fn, lp, h_in)

                def head_branch():
                    tgt = jax.lax.dynamic_index_in_dim(
                        tgt_mb, bi, 0, keepdims=False
                    )
                    loss_mb, (g_hp_mb, g_y, g_sp_mb) = jax.value_and_grad(
                        head, argnums=(0, 1, 3)
                    )(hp, y, tgt, sp)
                    return loss_mb.astype(f32), g_hp_mb, g_y, g_sp_mb

                loss_mb, g_hp_mb, g_y, g_sp_head = jax.lax.cond(
                    p == n_stages - 1,
                    head_branch,
                    lambda: (
                        jnp.zeros((), f32),
                        tmap(jnp.zeros_like, hp),
                        tmap(jnp.zeros_like, y),
                        tmap(jnp.zeros_like, sp),
                    ),
                )
                dh_out = tmap(
                    lambda a, b: jnp.where(p == n_stages - 1, a, b),
                    g_y,
                    carry["inc_g"],
                )
                g_lp_mb, g_h = vjp(dh_out)

                def embed_branch():
                    _, evjp = jax.vjp(
                        lambda e, s_: embed(
                            e,
                            jax.lax.dynamic_index_in_dim(
                                tok_mb, bi, 0, keepdims=False
                            ),
                            s_,
                        ),
                        ep,
                        sp,
                    )
                    return evjp(g_h)

                g_ep_mb, g_sp_embed = jax.lax.cond(
                    p == 0,
                    embed_branch,
                    lambda: (
                        tmap(jnp.zeros_like, ep),
                        tmap(jnp.zeros_like, sp),
                    ),
                )
                # Tied params: one accumulator, both contributions (at most
                # one is nonzero on any given stage).
                g_sp_mb = tmap(jnp.add, g_sp_head, g_sp_embed)
                return loss_mb, g_lp_mb, g_ep_mb, g_hp_mb, g_sp_mb, g_h

            (
                loss_mb, g_lp_mb, g_ep_mb, g_hp_mb, g_sp_mb, g_out
            ) = jax.lax.cond(
                do_bwd,
                bwd_slot,
                lambda: (
                    jnp.zeros((), f32),
                    tmap(jnp.zeros_like, lp),
                    tmap(jnp.zeros_like, ep),
                    tmap(jnp.zeros_like, hp),
                    tmap(jnp.zeros_like, sp),
                    zeros_h(),
                ),
            )

            acc = lambda a, b: a + b.astype(f32)  # noqa: E731
            new_carry = dict(
                fc=fc + do_fwd.astype(jnp.int32),
                bc=bc + do_bwd.astype(jnp.int32),
                stash=stash,
                # 4. Hand off: activations up, gradients down — both
                # unconditional every tick (deadlock freedom).
                inc_y=tmap(
                    lambda l: jax.lax.ppermute(l, axis, up), y_out
                ),
                inc_m=jax.lax.ppermute(m_out, axis, up),
                inc_g=tmap(
                    lambda l: jax.lax.ppermute(l, axis, down), g_out
                ),
                g_ep=jax.tree.map(acc, carry["g_ep"], g_ep_mb),
                g_lp=jax.tree.map(acc, carry["g_lp"], g_lp_mb),
                g_hp=jax.tree.map(acc, carry["g_hp"], g_hp_mb),
                g_sp=jax.tree.map(acc, carry["g_sp"], g_sp_mb),
                loss=carry["loss"] + loss_mb,
            )
            return new_carry, None

        out, _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
        inv_m = 1.0 / M
        loss = jax.lax.psum(out["loss"], axis) * inv_m
        cast = lambda g, ref: (g * inv_m).astype(ref.dtype)  # noqa: E731
        g_ep = jax.tree.map(
            cast, jax.lax.psum(out["g_ep"], axis), ep
        )
        g_hp = jax.tree.map(
            cast, jax.lax.psum(out["g_hp"], axis), hp
        )
        g_sp = jax.tree.map(
            cast, jax.lax.psum(out["g_sp"], axis), sp
        )
        g_lp = jax.tree.map(cast, out["g_lp"], lp)
        return loss, g_ep, g_lp, g_hp, g_sp

    rep = lambda tree: jax.tree.map(  # noqa: E731
        lambda l: P(*([None] * l.ndim)), tree
    )
    lp_spec = jax.tree.map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), layer_params
    )
    loss, g_ep, g_lp, g_hp, g_sp = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            rep(embed_params),
            lp_spec,
            rep(head_params),
            rep(sp_in),
            P(None, None),
            P(None, None),
        ),
        out_specs=(
            P(),
            rep(embed_params),
            lp_spec,
            rep(head_params),
            rep(sp_in),
        ),
        axis_names=frozenset({axis}),
        check_vma=False,
    )(embed_params, layer_params, head_params, sp_in, tokens, targets)
    if has_shared:
        return loss, (g_ep, g_lp, g_hp, g_sp)
    return loss, (g_ep, g_lp, g_hp)
