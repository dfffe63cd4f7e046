"""The routed-expert layer: dropless, and told which experts it holds.

One function for every family with routed experts.  A router scores ALL
``E`` experts per token (softmax over them, or a sigmoid each with a
selection bias, DeepSeek-V3's ``noaux_tc``), the ``k`` best are selected
and their scores normalised over all ``k`` and scaled (:func:`route`; a
softmax normalised over its selection IS the softmax over the selected
logits).  The router reads the experts' input, or, where a family places
it before attention, another tensor: such a layer calls :func:`route` on
the layer's input itself and hands :func:`routed_experts` the result.
An expert is a gated unit ``(act(h G) * (h U)) D`` with ``act`` the
``unit``: ``silu`` (SwiGLU) or ``relu`` (ReGLU).  The layer HOLDS the
contiguous experts ``first_held .. first_held + Eh - 1`` (``Eh`` is the
leading dimension of the expert weights: every expert, or one chip's share
under expert parallelism) and returns the part of the result its own
experts give: ``sum_{e in selected & held} w_e FFN_e(h)``.  What absent
experts would add is left out; nothing stands in for them or their
exchange.

No token is dropped and there is no capacity: the ``T*k`` assignments are
sorted by expert (absent experts last), so the rows the held experts work
on are the first ``M = group_sizes.sum()`` of the sorted order, and the
expert FFNs run as grouped matrix products over them
(``jax.lax.ragged_dot``; XLA:TPU lowers it to its own grouped-matmul
kernel, ``ragged-dot-*`` in a trace, which only visits rows that belong to
a group).  Only those rows are touched.  Everything on the sorted side runs
over a CHUNK of rows: a first one of ``R`` rows, then the overflow in
chunks of ``R2`` rows, in a loop whose trip count is read on the device
from the routing the step just made:

* ``R`` comes from shapes alone (``_row_bound``): the held share of the
  assignments, ``T*k*Eh/E``, with a third of room over it, rounded up to
  the grouped product's row tile of 512, at most ``T*k``.  A layer that
  holds every expert runs ONE chunk of ``T*k`` rows; a share of a quarter
  runs a third of the rows while its routing stays within the room, and
  ``ceil((M - R) / R2)`` more chunks whenever it does not, ``R2`` an
  eighth of ``R`` up to the row tile (``_tail_rows``): what passes the
  room is a few percent of the order, so an overflow costs about what
  its rows cost.  For ANY routing the chunks cover all ``M`` rows:
  nothing is dropped or approximated, an uneven routing costs chunks
  (``stats["row_chunks"]`` counts them).
* A chunk gathers its rows of ``h``, clips the group sizes to its range
  and runs the three grouped products on its rows.  In the FIRST chunk
  every token then adds, in float32, the rows that hold one of its choices
  (gathers by the inverse permutation, choice by choice; never a
  scatter-add over ``R`` rows, which measured 1.5 ms where the six gathers
  take 0.7, PERF.md section 6).  Those gathers are ``k * T`` rows whatever
  the chunk holds, so a chunk of the overflow adds ITS rows to their
  tokens instead (``_add_rows``, forward and backward): sorted by token,
  through one more grouped product whose left operand is the one-hot of a
  row's token within a block of 512 tokens.  Its rows' results are
  weighted in float32 and rounded to the products' dtype once more on the
  way into that product; the sums are float32.
* The loop's trip count is dynamic, and such a loop has no transpose, so
  the sorted side is ONE ``jax.custom_vjp`` whose backward is the same
  loop by hand.  A chunk's backward is written out (``_chunk_bwd``): from
  the chunk's gate and up products it takes SIX grouped products (the
  down product transposed, three weight gradients, the gate's and up's
  inputs) and no forward product; the routing weights' gradient is
  ``sum_f act * (g @ e_down^T)``, so the down product is not run again
  for it.  Gradients of ``h`` and the weights accumulate in float32, the
  expert matrices' chunk by chunk.
* The forward runs ONCE for the first chunk, which always runs and runs
  outside the loop (with one chunk, the usual case, nothing is carried
  through a loop): the forward rule returns its gate and up products
  ``(R, F)`` beside the inputs, named ``moe_gate`` and ``moe_up``, and the
  layer's result is named ``moe_out``, so a rematerialised block
  (``ops/remat.py``) keeps them and replays neither the gather, the
  products nor the sum; the counter ``moe.first_chunk{forward=kept}``
  counts the backward rule's traces.  The kept products are rows of the
  order the forward's choice sorted, so the choice is named too
  (``moe_selected``) and a replay sorts by it: its own scores round
  otherwise in bfloat16, a near tie flips, and every row behind the
  flipped one would meet another token's products.  Chunks past the
  first recompute their gate and up products in the backward loop: their
  number is read on the device, so nothing of theirs can be kept without
  a worst-case buffer.

Scopes (HLO metadata only): ``router``, ``dispatch``, ``experts``,
``combine``, to be entered under the caller's ``moe`` scope.  Counters,
once a traced layer: ``moe.unit{kind=silu|relu}`` and
``moe.router_input{from=expert_input|layer_input}`` (routed here from the
experts' input, or by the caller from another tensor).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry as _telemetry

__all__ = ["Routing", "route", "routed_experts"]


# The T*k assignments are kept CHOICE-major (row ``c * T + t`` is token
# ``t``'s ``c``-th choice), so a sorted row's token is ``perm % T``, and the
# sum over a token's choices adds whole (T, D) slabs.

# A chunk holds the held share of the T*k assignments with this much room
# over it, rounded up to the grouped product's row tile.
_HEADROOM = (4, 3)
_ROW_TILE = 512


def _row_bound(n_rows: int, n_held: int, n_experts: int) -> int:
    """``R``, the rows of one chunk of the sorted order, from shapes alone.
    With every expert held it is ``n_rows``: one chunk, the whole order."""
    num, den = _HEADROOM
    share = -(-n_rows * n_held * num // (n_experts * den))
    return min(n_rows, -(-share // _ROW_TILE) * _ROW_TILE)


# A chunk of the overflow holds this share of the first chunk's rows.
_TAIL_SHARE = 8


def _tail_rows(rows: int) -> int:
    """``R2``, the rows of a chunk past the first: an eighth of the first
    chunk's, rounded up to the row tile."""
    return min(rows, -(-rows // (_TAIL_SHARE * _ROW_TILE)) * _ROW_TILE)


def _n_tail(group_sizes, rows):
    """The chunks past the first: ``ceil((M - R) / R2)``."""
    tail = _tail_rows(rows)
    return (jnp.maximum(group_sizes.sum() - rows, 0) + tail - 1) // tail


def _n_chunks(group_sizes, rows):
    return jnp.minimum(group_sizes.sum(), 1) + _n_tail(group_sizes, rows)


def _first_chunk(rows, perm, inv, group_sizes):
    """The first ``rows`` rows of the sorted order: the assignment each row
    holds (``perm``'s slice), the group sizes clipped to the chunk, and for
    every (choice, token) the row of the chunk that holds it (``at``,
    clamped) and whether one does."""
    ends = jnp.cumsum(group_sizes)
    sizes = jnp.clip(jnp.minimum(ends, rows) - (ends - group_sizes), 0)
    # The held rows are the first ``ends[-1]`` of the order; the grouped
    # products never compute the others, so no sum may read them.
    ok = (inv < rows) & (inv < ends[-1])
    return perm[:rows], sizes, jnp.clip(inv, 0, rows - 1), ok


def _tail_chunk(i, rows, perm, group_sizes):
    """Chunk ``i`` of the overflow, the ``R2`` rows of the sorted order
    from ``rows + i * R2``: the assignment each row holds, the group sizes
    clipped to the chunk, and which of its rows a held expert works on."""
    tail = _tail_rows(rows)
    lo = rows + i * tail
    ends = jnp.cumsum(group_sizes)
    sizes = jnp.clip(
        jnp.minimum(ends, lo + tail) - jnp.maximum(ends - group_sizes, lo), 0
    )
    held = lo + jnp.arange(tail, dtype=ends.dtype) < ends[-1]
    return jax.lax.dynamic_slice(perm, (lo,), (tail,)), sizes, held


def _gate_up(xs, e_gate, e_up, sizes):
    with jax.named_scope("experts"):
        return (
            jax.lax.ragged_dot(xs, e_gate, sizes),
            jax.lax.ragged_dot(xs, e_up, sizes),
        )


# The gated unit of an expert, by name: the gate product's activation
# times the up product.
_UNITS = {
    "silu": lambda gate, up: jax.nn.silu(gate) * up,
    "relu": lambda gate, up: jax.nn.relu(gate) * up,
}


def _down(gate, up, e_down, sizes, unit="silu"):
    with jax.named_scope("experts"):
        return jax.lax.ragged_dot(_UNITS[unit](gate, up), e_down, sizes)


# The two transposes of ``ragged_dot(x (R, K), w (Eh, K, N), sizes)``, as
# its own transpose rule writes them.
_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
)


def _dot_t(dy, w, sizes):
    """``dy (R, N)`` -> the gradient of ``x``, ``(R, K)``."""
    return jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), sizes)


def _dot_w(x, dy, sizes):
    """``x (R, K)``, ``dy (R, N)`` -> the gradient of ``w``, ``(Eh, K, N)``."""
    return jax.lax.ragged_dot_general(x, dy, sizes, _ROWS_CONTRACTED)


def _chunk_bwd(xs, gate, up, e_gate, e_up, e_down, sizes, g, w_rows,
               unit="silu"):
    """The backward of ``y = w_rows * down(act(gate, up))`` over a chunk's
    rows from its gate and up products, ``g (R, D)`` float32 the
    UNWEIGHTED gradient of each row's result: six grouped products, none
    of them a forward one.  -> ``(dxs, (d e_gate, d e_up, d e_down),
    dw_rows (R,) float32)``."""
    with jax.named_scope("experts"):
        act, pull = jax.vjp(_UNITS[unit], gate, up)
        # dw[r] = sum_d y[r, d] g[r, d] = sum_f act[r, f] (g @ e_down^T)[r, f]
        gd = _dot_t(g.astype(act.dtype), e_down, sizes).astype(jnp.float32)
        dw_rows = (act.astype(jnp.float32) * gd).sum(axis=-1)
        d_gate, d_up = pull((gd * w_rows[:, None]).astype(act.dtype))
        dy = (g * w_rows[:, None]).astype(act.dtype)
        d_experts = (
            _dot_w(xs, d_gate, sizes), _dot_w(xs, d_up, sizes),
            _dot_w(act, dy, sizes),
        )
        dxs = _dot_t(d_gate, e_gate, sizes) + _dot_t(d_up, e_up, sizes)
    return dxs, d_experts, dw_rows


def _per_token(table, at, ok, w=None):
    """``sum_c table[at[c, t]]`` (times ``w[c, t]``) over the choices with
    ``ok[c, t]``: ``(T, D)`` float32 from a chunk's ``(R, D)`` rows."""
    out = 0.0
    for c in range(at.shape[0]):
        picked = jnp.take(table, at[c], axis=0, mode="clip")
        picked = picked.astype(jnp.float32)
        if w is not None:
            picked = picked * w[c][:, None]
        out = out + jnp.where(ok[c][:, None], picked, 0.0)
    return out


# A chunk of the overflow adds its rows to their tokens in blocks of this
# many tokens.
_TOKEN_BLOCK = 512


def _add_rows(out, at, rows, held):
    """``out[at[r]] += rows[r]`` over the rows with ``held[r]``: how a
    chunk of the overflow reaches its tokens, ``out (T, D)`` float32.  Not a
    scatter-add, which the TPU runs row by row (0.75 us a row of 2,560:
    PERF.md section 6, PR 37), but a grouped product: the rows sorted by
    token, a block of 512 tokens a group, each row's left operand the
    one-hot of its token within the block, so group ``b``'s product is
    block ``b``'s ``(512, D)`` sums; a token that owns several rows of the
    chunk gets them all."""
    t = out.shape[0]
    n_blocks = -(-t // _TOKEN_BLOCK)
    # Rows no held expert works on sort last, into no group.
    key = jnp.where(held, at, n_blocks * _TOKEN_BLOCK)
    order = jnp.argsort(key)
    key = key[order]
    block, col = key // _TOKEN_BLOCK, key % _TOKEN_BLOCK
    sizes = (
        block[:, None] == jnp.arange(n_blocks, dtype=key.dtype)[None, :]
    ).sum(axis=0, dtype=jnp.int32)
    onehot = col[:, None] == jnp.arange(_TOKEN_BLOCK, dtype=key.dtype)[None, :]
    sums = jax.lax.ragged_dot_general(
        onehot.astype(rows.dtype), jnp.take(rows, order, axis=0), sizes,
        _ROWS_CONTRACTED, preferred_element_type=jnp.float32,
    )
    return out + sums.reshape(n_blocks * _TOKEN_BLOCK, -1)[:t]


def _forward(rows, unit, h, e_gate, e_up, e_down, w, perm, inv, group_sizes):
    """``(out, (gate, up))``: the sorted side's result and the first
    chunk's two products, named for a remat policy."""
    t = h.shape[0]

    def add_tail(i, out):
        flat, sizes, held = _tail_chunk(i, rows, perm, group_sizes)
        with jax.named_scope("dispatch"):
            xs = jnp.take(h, flat % t, axis=0, mode="clip")
        y = _down(*_gate_up(xs, e_gate, e_up, sizes), e_down, sizes, unit)
        with jax.named_scope("combine"):
            w_rows = jnp.take(w.reshape(-1), flat, mode="clip")
            y = (y.astype(jnp.float32) * w_rows[:, None]).astype(y.dtype)
            return _add_rows(out, flat % t, y, held)

    # The first chunk outside the loop: with one chunk, the usual case,
    # nothing is carried through a loop that does not run.
    flat, sizes, at, ok = _first_chunk(rows, perm, inv, group_sizes)
    with jax.named_scope("dispatch"):
        xs = jnp.take(h, flat % t, axis=0, mode="clip")
    gate, up = _gate_up(xs, e_gate, e_up, sizes)
    kept = checkpoint_name(gate, "moe_gate"), checkpoint_name(up, "moe_up")
    y = _down(*kept, e_down, sizes, unit)
    with jax.named_scope("combine"):
        out = _per_token(y, at, ok, w)
    return jax.lax.fori_loop(0, _n_tail(group_sizes, rows), add_tail, out), kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sorted_side(rows, unit, h, e_gate, e_up, e_down, w, perm, inv,
                 group_sizes):
    """``out[t] = sum_c w[c, t] * FFN_{expert of (c, t)}(h[t])`` over the
    held choices, ``(T, D)`` float32, in a first chunk of ``rows`` rows of
    the sorted order and ``ceil((M - rows) / R2)`` of the overflow (``M =
    group_sizes.sum()``, read on the device).  ``w``,
    ``inv`` ``(k, T)``; ``perm`` padded by ``rows``."""
    return _forward(
        rows, unit, h, e_gate, e_up, e_down, w, perm, inv, group_sizes
    )[0]


def _sorted_side_fwd(rows, unit, *args):
    out, kept = _forward(rows, unit, *args)
    return out, (args, kept)


def _sorted_side_bwd(rows, unit, res, dout):
    # The trip count is read from the routing, and a loop of unknown
    # length has no transpose: the backward is the same loop, written out.
    (h, e_gate, e_up, e_down, w, perm, inv, group_sizes), kept = res
    t = h.shape[0]
    _telemetry.counter("moe.first_chunk", forward="kept").add()

    def chunk_bwd(flat, sizes, products=None):
        with jax.named_scope("dispatch"):
            xs = jnp.take(h, flat % t, axis=0, mode="clip")
        if products is None:
            products = _gate_up(xs, e_gate, e_up, sizes)
        with jax.named_scope("combine"):
            g = jnp.take(dout, flat % t, axis=0, mode="clip")
            w_rows = jnp.take(w.reshape(-1), flat, mode="clip")
        return _chunk_bwd(
            xs, *products, e_gate, e_up, e_down, sizes, g, w_rows, unit
        )

    def tail(i, carry):
        dh, d_experts, dw = carry
        flat, sizes, held = _tail_chunk(i, rows, perm, group_sizes)
        dxs, more, dw_rows = chunk_bwd(flat, sizes)
        with jax.named_scope("combine"):
            # ``R2`` scalars: a scatter-add runs element by element.
            dw = dw.reshape(-1).at[flat].add(jnp.where(held, dw_rows, 0.0))
        with jax.named_scope("dispatch"):
            dh = _add_rows(dh, flat % t, dxs, held)
        return dh, jax.tree.map(jnp.add, d_experts, more), dw.reshape(w.shape)

    flat, sizes, at, ok = _first_chunk(rows, perm, inv, group_sizes)
    dxs, d_experts, dw_rows = chunk_bwd(flat, sizes, kept)
    with jax.named_scope("combine"):
        dw = jnp.where(ok, jnp.take(dw_rows, at, mode="clip"), 0.0)
    with jax.named_scope("dispatch"):
        dh = _per_token(dxs, at, ok)
    dh, d_experts, dw = jax.lax.fori_loop(
        0, _n_tail(group_sizes, rows), tail, (dh, d_experts, dw)
    )
    return (
        dh.astype(h.dtype), *d_experts, dw.astype(w.dtype), None, None, None
    )


_sorted_side.defvjp(_sorted_side_fwd, _sorted_side_bwd)


class Routing(NamedTuple):
    """What :func:`route` decides for ``T`` tokens: ``scores (T, E)``
    float32, the experts chosen ``selected (T, k)`` and their weights
    ``w (T, k)``."""

    scores: jax.Array
    selected: jax.Array
    w: jax.Array


def route(x, router_w, *, top_k: int, gates: str = "softmax", bias=None,
          scale: float = 1.0) -> Routing:
    """``x (T, D)`` -> the :class:`Routing` of its tokens: ``router_w (D,
    E)`` scores all ``E`` experts in float32 (``gates``: ``"softmax"``
    over the experts, or ``"sigmoid"`` per expert); ``bias (E,)`` is added
    to the scores for the SELECTION only; the selected scores are
    normalised over all ``top_k`` (held or not) and multiplied by
    ``scale``."""
    with jax.named_scope("router"):
        logits = jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
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
        # Kept by a rematerialised block beside the products: they are
        # rows of the order THIS choice sorts, and a replay whose scores
        # round otherwise flips a near tie and sorts another.
        selected = checkpoint_name(selected, "moe_selected")
        w = jnp.take_along_axis(scores, selected, axis=-1)
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scale
    return Routing(scores, selected, w)


def routed_experts(
    h, router_w, e_gate, e_up, e_down, *, top_k: int, gates: str = "softmax",
    bias=None, scale: float = 1.0, first_held: int = 0, unit: str = "silu",
    routing: Routing = None,
):
    """``h (T, D)`` -> ``(out (T, D), stats)``.

    ``e_gate``, ``e_up`` ``(Eh, D, F)`` and ``e_down (Eh, F, D)`` are the
    held experts ``first_held .. first_held + Eh - 1``, each the gated
    unit ``(act(h G) * (h U)) D`` with ``act`` named by ``unit``:
    ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU).  The tokens are routed by
    :func:`route` from ``h`` itself (``router_w``, ``top_k``, ``gates``,
    ``bias``, ``scale`` are its arguments), unless ``routing`` hands over
    what the caller's own :func:`route` decided from ANOTHER tensor (a
    router placed before attention reads the layer's input, its experts
    the post-attention norm's output): those five are then not read.

    The sorted side runs in ``ceil(M / R)`` row chunks, ``M`` the
    assignments to held experts and ``R`` a bound from the shapes (module
    docstring): the cost follows the rows held, not ``T * top_k``.

    ``stats``: ``scores (T, E)`` and ``selected (T, top_k)`` for a
    family's balance loss, ``group_sizes (Eh,)`` the assignments each held
    expert received, ``local_assignments`` their sum ``M``,
    ``load_max_over_mean``, the busiest held expert over the held mean,
    and ``row_chunks``, ``ceil(M / R)``: the chunks that held a row (one
    while the routing stays within the room ``R`` leaves).
    """
    if unit not in _UNITS:
        raise ValueError(f"unknown unit: {unit!r} ({'|'.join(_UNITS)})")
    t, n_held = h.shape[0], e_gate.shape[0]
    _telemetry.counter("moe.unit", kind=unit).add()
    _telemetry.counter(
        "moe.router_input",
        **{"from": "expert_input" if routing is None else "layer_input"},
    ).add()
    if routing is None:
        routing = route(
            h, router_w, top_k=top_k, gates=gates, bias=bias, scale=scale
        )
    scores, selected, w = routing
    top_k, n_experts = selected.shape[1], scores.shape[1]

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
        rows = _row_bound(t * top_k, n_held, n_experts)

    out = _sorted_side(
        rows, unit, h, e_gate, e_up, e_down, w.T, jnp.pad(perm, (0, rows)),
        inv.reshape(top_k, t), group_sizes,
    ).astype(h.dtype)
    out = checkpoint_name(out, "moe_out")

    sizes = group_sizes.astype(jnp.float32)
    stats = {
        "scores": scores, "selected": selected, "group_sizes": group_sizes,
        "local_assignments": sizes.sum(),
        "load_max_over_mean": sizes.max() / jnp.maximum(sizes.mean(), 1e-9),
        "row_chunks": _n_chunks(group_sizes, rows),
    }
    return out, stats
