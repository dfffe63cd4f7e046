"""The routed-expert layer: dropless, and told which experts it holds.

One function for every family with routed experts.  A router scores ALL
``E`` experts per token (softmax over them, or a sigmoid each with a
selection bias, DeepSeek-V3's ``noaux_tc``), the ``k`` best are selected
and their scores normalised over all ``k`` and scaled; the layer HOLDS the
contiguous experts ``first_held .. first_held + Eh - 1`` (``Eh`` is the
leading dimension of the expert weights: every expert, or one chip's share
under expert parallelism) and returns the part of the result its own
experts give: ``sum_{e in selected & held} w_e FFN_e(h)``.  What absent
experts would add is left out; nothing stands in for them or their
exchange.

No token is dropped and there is no capacity: the ``T*k`` assignments are
sorted by expert (absent experts last) and the expert FFNs run as grouped
matrix products over the sorted rows (``jax.lax.ragged_dot``; XLA:TPU
lowers it to its own grouped-matmul kernel, ``ragged-dot-*`` in a trace,
which only visits rows that belong to a group).  Gathers both ways: the
dispatch's and the combine's backward passes are gathers by the inverse
permutation, never a scatter-add.

Scopes (HLO metadata only): ``router``, ``dispatch``, ``experts``,
``combine``, to be entered under the caller's ``moe`` scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["routed_experts"]


# The T*k assignments are kept CHOICE-major (row ``c * T + t`` is token
# ``t``'s ``c``-th choice): reshaped to ``(k, T, D)`` the sum over a token's
# choices runs over the major axis, whole (T, D) slabs added, where ``(T, k,
# D)`` would pad ``k`` up to the sublane tile.


@jax.custom_vjp
def _dispatch(h, perm, inv, held):
    """Rows of ``h (T, D)`` in sorted-assignment order ``(k*T, D)``."""
    return jnp.take(h, perm % h.shape[0], axis=0)


def _dispatch_fwd(h, perm, inv, held):
    return _dispatch(h, perm, inv, held), (inv, held)


def _dispatch_bwd(res, dxs):
    inv, held = res
    # Back in (choice, token) order; rows of absent experts were never
    # computed by the grouped product, so they are masked, not trusted.
    d = jnp.take(dxs, inv, axis=0).reshape(*held.shape, dxs.shape[-1])
    d = jnp.where(held[..., None], d.astype(jnp.float32), 0.0).sum(axis=0)
    return d.astype(dxs.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, w, held, perm, inv):
    """``out[t] = sum_c w[c, t] * y[row of (c, t)]`` over held choices, in
    float32; ``y (k*T, D)`` is in sorted order, ``w``/``held`` ``(k, T)``."""
    yk = jnp.take(y, inv, axis=0).reshape(*w.shape, -1).astype(jnp.float32)
    return jnp.where(held[..., None], yk * w[..., None], 0.0).sum(axis=0)


def _combine_fwd(y, w, held, perm, inv):
    return _combine(y, w, held, perm, inv), (y, w, held, perm, inv)


def _combine_bwd(res, dout):
    y, w, held, perm, inv = res
    yk = jnp.take(y, inv, axis=0).reshape(*w.shape, -1).astype(jnp.float32)
    dw = jnp.where(held, (yk * dout[None]).sum(axis=-1), 0.0)
    dyk = jnp.where(held[..., None], dout[None] * w[..., None], 0.0)
    dy = jnp.take(
        dyk.reshape(-1, dyk.shape[-1]).astype(y.dtype), perm, axis=0
    )
    return dy, dw.astype(w.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(
    h, router_w, e_gate, e_up, e_down, *, top_k: int, gates: str = "softmax",
    bias=None, scale: float = 1.0, first_held: int = 0,
):
    """``h (T, D)`` -> ``(out (T, D), stats)``.

    ``router_w (D, E)`` scores all ``E`` experts in float32; ``e_gate``,
    ``e_up`` ``(Eh, D, F)`` and ``e_down (Eh, F, D)`` are the held experts
    ``first_held .. first_held + Eh - 1`` (SwiGLU).  ``gates``:
    ``"softmax"`` over the experts, or ``"sigmoid"`` per expert; ``bias
    (E,)`` is added to the scores for the SELECTION only; the selected
    scores are normalised over all ``top_k`` (held or not) and multiplied
    by ``scale``.

    ``stats``: ``scores (T, E)`` and ``selected (T, top_k)`` for a
    family's balance loss, ``group_sizes (Eh,)`` the assignments each held
    expert received, ``local_assignments`` their sum, and
    ``load_max_over_mean``, the busiest held expert over the held mean.
    """
    t, n_held = h.shape[0], e_gate.shape[0]
    with jax.named_scope("router"):
        logits = jnp.dot(
            h.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if gates == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        elif gates == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown gates: {gates!r} (softmax|sigmoid)")
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        _, selected = jax.lax.top_k(choice, top_k)  # (T, K)
        w = jnp.take_along_axis(scores, selected, axis=-1)
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scale

    with jax.named_scope("dispatch"):
        local = selected.T - first_held  # (K, T): choice-major
        held = (local >= 0) & (local < n_held)
        # Absent experts sort last, behind every group.
        key = jnp.where(held, local, n_held).reshape(t * top_k)
        perm = jnp.argsort(key, stable=True)
        inv = jnp.zeros_like(perm).at[perm].set(
            jnp.arange(t * top_k, dtype=perm.dtype)
        )
        group_sizes = (
            key[:, None] == jnp.arange(n_held, dtype=key.dtype)[None, :]
        ).sum(axis=0, dtype=jnp.int32)
        xs = _dispatch(h, perm, inv, held)

    with jax.named_scope("experts"):
        gated = jax.nn.silu(jax.lax.ragged_dot(xs, e_gate, group_sizes))
        up = jax.lax.ragged_dot(xs, e_up, group_sizes)
        y = jax.lax.ragged_dot(gated * up, e_down, group_sizes)

    with jax.named_scope("combine"):
        out = _combine(y, w.T, held, perm, inv).astype(h.dtype)

    sizes = group_sizes.astype(jnp.float32)
    stats = {
        "scores": scores, "selected": selected, "group_sizes": group_sizes,
        "local_assignments": sizes.sum(),
        "load_max_over_mean": sizes.max() / jnp.maximum(sizes.mean(), 1e-9),
    }
    return out, stats
