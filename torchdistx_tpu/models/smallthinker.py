"""SmallThinker family (PowerInfer/SmallThinker-21BA3B-Instruct,
arXiv:2507.20984): every layer an expert layer whose ROUTER READS THE
LAYER'S INPUT, before the attention's norm and before attention; ReGLU
experts; window and full attention layers mixed — the training path.

No bias anywhere; every RMS norm computes in float32.  Per layer, ``x`` its
input::

    l = x W_r                                  # (T, E) float32: the router
    S = top_k(l);  w = softmax(l[S])           #   reads x, not the experts' input
    h = rmsnorm(x, attn_norm)
    q, k, v = (h W_q, h W_k, h W_v) per head   # no q/k norm, no gate
    layer_types[i] == "sliding_attention":
        q, k = rope(q, k)                      # half-split, all head_dim
        key j visible to query t  iff  0 <= t - j < window
    "full_attention":                          # NO position term at all
        key j visible to query t  iff  j <= t
    a = softmax(q k^T / sqrt(head_dim)) v      # query head n on kv head n // G
    y = x + a W_o
    u = rmsnorm(y, mlp_norm)
    out = y + sum_{e in S} w_e (relu(u G_e) * (u U_e)) D_e

and ``logits = rmsnorm(x, norm) W_head`` (untied).  The softmax over the
selected logits is the softmax over all ``E`` normalised over the
selection, which is what :func:`~torchdistx_tpu.ops.routed_experts.route`
computes; the routed sum is :func:`~torchdistx_tpu.ops.routed_experts
.routed_experts`, the one routed layer of every family, handed the routing
made before attention and the unit ``relu``.  The layer is told which
experts it holds (``n_experts_held`` / ``first_expert_held``; the router
stays ``n_experts`` wide); what absent experts would add is left out.  No
shared expert, no dense layer.

A layer's kind is STATIC.  One stack, ``moe_layers``, ``(L, ...)`` in layer
order beside an EMPTY ``dense_layers`` (the routed families' tree: what
walks one walks all); it runs as one scan over the whole periods of its
kinds, the period's layers one after another in the body
(:func:`~torchdistx_tpu.models.afmoe._run_stack`), each rematerialised with
``ops.remat.REMAT_POLICY``: a block keeps its input, ``flash_out``,
``flash_lse``, ``moe_selected``, ``moe_gate``, ``moe_up``.  The head and
the loss go in blocks of rows (``_common.blocked_head_ce``, which takes
the head's gradient in the forward pass): at 16,384 positions the float32
logits of a 37,984-row head are 2.5 GB.  ``loss_fn`` returns
``(loss, aux)`` (``LOSS_HAS_AUX``) with ``aux["moe"]`` the step's routing
counts.

Scopes: ``embed``; ``moe/router`` entered FIRST in a layer; ``attn`` with
``norm``, ``proj_in``, ``rope`` (window layers only), ``proj_out``; ``moe``
with ``dispatch``, ``experts``, ``combine`` and the norm; ``head``;
``stack``.  Counters: ``moe.experts_held``, ``moe.experts_total``, the
routed layer's ``moe.router_input{from=layer_input}`` and
``moe.unit{kind=relu}``, the head's ``head.ce{grad=forward}`` and
``head.row_blocks``, and attention's own.

Not here yet: a cache (no serving path), and what the early router is FOR
in the published system, prefetching the chosen experts' weights while
attention runs, which only a served, offloaded path can show.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from ..ops.attention import attention
from ..ops.remat import REMAT_POLICY
from ..ops.routed_experts import route, routed_experts
from . import afmoe as afmoe_mod
from ._common import blocked_head_ce
from . import llama as llama_mod

__all__ = [
    "SmallThinkerConfig",
    "LOSS_HAS_AUX",
    "smallthinker_test",
    "init_params",
    "abstract_params",
    "param_specs",
    "forward",
    "loss_fn",
    "num_params",
]

# loss_fn returns (loss, aux): make_train_step differentiates with has_aux
# and merges aux into the step's metrics.
LOSS_HAS_AUX = True

WINDOW, FULL = afmoe_mod.WINDOW, afmoe_mod.FULL


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    expert_dim: int = 768
    n_experts: int = 64  # the router's width
    experts_per_token: int = 6
    # The share held here: all experts unless told otherwise.
    n_experts_held: Optional[int] = None
    first_expert_held: int = 0
    window: int = 4096
    # One kind a layer; None: every fourth layer full from layer 0, as
    # published (``sliding_window_layout`` 0, 1, 1, 1, ...).
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        kinds = self.layer_types
        if kinds is None:
            kinds = (FULL if i % 4 == 0 else WINDOW for i in range(self.n_layers))
        kinds = tuple(kinds)
        object.__setattr__(self, "layer_types", kinds)
        if len(kinds) != self.n_layers or set(kinds) - {WINDOW, FULL}:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers as "
                f"{WINDOW!r} or {FULL!r}, got {kinds}"
            )
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held


def smallthinker_test() -> SmallThinkerConfig:
    """Two periods of full, window, window, window; 6 query heads on 2."""
    return SmallThinkerConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=6, n_kv_heads=2,
        head_dim=16, expert_dim=32, n_experts=8, experts_per_token=2,
        window=24, dtype=jnp.float32, remat=False,
    )


def _shapes(cfg: SmallThinkerConfig) -> dict:
    D, V, L = cfg.dim, cfg.vocab_size, cfg.n_layers
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Eh, F = cfg.n_experts, cfg.held, cfg.expert_dim
    return {
        "embed": {"weight": (V, D)},
        "dense_layers": {},  # the family has none
        "moe_layers": {
            "attn_norm": (L, D), "mlp_norm": (L, D),
            "wq": (L, D, H * hd), "wk": (L, D, Hkv * hd),
            "wv": (L, D, Hkv * hd), "wo": (L, H * hd, D),
            "router": (L, D, E),
            "e_gate": (L, Eh, D, F), "e_up": (L, Eh, D, F),
            "e_down": (L, Eh, F, D),
        },
        "norm": {"weight": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def _is_shape(x):
    return isinstance(x, tuple)


def abstract_params(cfg: SmallThinkerConfig):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype), _shapes(cfg),
        is_leaf=_is_shape,
    )


def num_params(cfg: SmallThinkerConfig) -> int:
    return sum(
        math.prod(s) for s in jax.tree.leaves(_shapes(cfg), is_leaf=_is_shape)
    )


def param_specs(
    cfg: SmallThinkerConfig, *, tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
):
    """FSDP + Megatron-TP specs matching :func:`abstract_params`: column
    projections shard their out dim over ``tp``, row projections their in
    dim, the other large dim over ``fsdp``; norms and router replicate.
    The held experts are NOT spread over a mesh axis: a chip of an
    expert-parallel deployment runs this program with its own
    ``first_expert_held``."""
    col, row = P(None, fsdp, tp), P(None, tp, fsdp)
    return {
        "embed": {"weight": P(fsdp, tp)},
        "dense_layers": {},
        "moe_layers": {
            "attn_norm": P(), "mlp_norm": P(),
            "wq": col, "wk": col, "wv": col, "wo": row, "router": P(),
            "e_gate": P(None, None, fsdp, tp), "e_up": P(None, None, fsdp, tp),
            "e_down": P(None, None, tp, fsdp),
        },
        "norm": {"weight": P()},
        "lm_head": {"weight": P(fsdp, tp)},
    }


def init_params(key, cfg: SmallThinkerConfig):
    """N(0, 0.02) for every matrix (the router's among them), N(0, 1) for
    the embedding's rows (``smallthinker_torch`` says why), ones for norms;
    per-leaf ``fold_in`` keys."""
    import zlib

    def leaf(path, shape):
        if path[-1].endswith("norm") or path[0] == "norm":
            return jnp.ones(shape, dtype=cfg.dtype)
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        std = 1.0 if path[0] == "embed" else 0.02
        return (
            jax.random.normal(leaf_key, shape, dtype=jnp.float32) * std
        ).astype(cfg.dtype)

    def walk(tree, path=()):
        if _is_shape(tree):
            return leaf(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(_shapes(cfg))


# ---------------------------------------------------------------------------
# Forward


def _attn(x, lp, cfg: SmallThinkerConfig, kind, *, mesh, attn_impl):
    b, s, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("norm"):
            h = llama_mod._rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        with jax.named_scope("proj_in"):
            q = (h @ lp["wq"]).reshape(b, s, H, hd)
            k = (h @ lp["wk"]).reshape(b, s, Hkv, hd)
            v = (h @ lp["wv"]).reshape(b, s, Hkv, hd)
        if kind == WINDOW:
            with jax.named_scope("rope"):
                cos, sin = llama_mod._rope_tables(
                    jnp.arange(s)[None], cfg.rope_theta, hd // 2, x.dtype
                )
                q = llama_mod._rope_apply(q, cos, sin)
                k = llama_mod._rope_apply(k, cos, sin)
        a = attention(
            q, k, v, causal=True, impl=attn_impl, mesh=mesh,
            window=cfg.window if kind == WINDOW else None,
        )
        with jax.named_scope("proj_out"):
            return x + a.reshape(b, s, H * hd) @ lp["wo"]


def _build_block(cfg: SmallThinkerConfig, *, mesh=None, attn_impl="auto"):
    """``block(kind)``: a layer of that kind as ``x, lp -> (x, (assignments
    to held experts, busiest held expert over their mean, row chunks the
    routed layer ran))``."""

    def block_of(kind):
        def block(x, lp):
            b, s, d = x.shape
            with jax.named_scope("moe"):
                # from the layer's INPUT, before attention
                routing = route(
                    x.reshape(b * s, d), lp["router"],
                    top_k=cfg.experts_per_token,
                )
            y = _attn(x, lp, cfg, kind, mesh=mesh, attn_impl=attn_impl)
            with jax.named_scope("moe"):
                u = llama_mod._rmsnorm(y, lp["mlp_norm"], cfg.norm_eps)
                out, stats = routed_experts(
                    u.reshape(b * s, d), lp["router"], lp["e_gate"],
                    lp["e_up"], lp["e_down"], top_k=cfg.experts_per_token,
                    first_held=cfg.first_expert_held, unit="relu",
                    routing=routing,
                )
                y = y + out.reshape(b, s, d)
            return y, (
                stats["local_assignments"], stats["load_max_over_mean"],
                stats["row_chunks"],
            )

        if cfg.remat:
            return jax.checkpoint(block, policy=REMAT_POLICY)
        return block

    return block_of


def _forward_hidden(params, tokens, cfg, *, mesh=None, attn_impl="auto"):
    """Embedding + the stack -> ``(x, moe)`` with ``moe`` the step's
    routing counts (device scalars)."""
    _telemetry.counter("moe.experts_held").add(cfg.held)
    _telemetry.counter("moe.experts_total").add(cfg.n_experts)
    x = llama_mod._embed(params, tokens, cfg)
    block_of = _build_block(cfg, mesh=mesh, attn_impl=attn_impl)
    # ``stack``: the periods' reshape, the scan's own work and each layer's
    # weights indexed out of its period; the blocks' scopes are innermost.
    with jax.named_scope("stack"):
        x, (assigned, load, chunks) = afmoe_mod._run_stack(
            x, params["moe_layers"], cfg.layer_types, block_of
        )
    return x, {
        "local_assignments": assigned.sum(),
        "load_max_over_mean": load.mean(),
        "row_chunks": chunks.sum(),
    }


def _head_ce(params, x, targets, cfg: SmallThinkerConfig):
    """Final norm, then the head and mean cross-entropy in blocks of rows
    (:func:`~torchdistx_tpu.models._common.blocked_head_ce`): the float32
    logits of a 37,984-row head at 16,384 positions are 2.5 GB."""
    with jax.named_scope("head"):
        h = llama_mod._rmsnorm(x, params["norm"]["weight"], cfg.norm_eps)
        return blocked_head_ce(
            h.reshape(-1, h.shape[-1]),
            params["lm_head"]["weight"].astype(cfg.dtype),
            targets.reshape(-1), vocab_major=False,
        )


def forward(params, tokens, cfg: SmallThinkerConfig, *, mesh=None,
            attn_impl: str = "auto"):
    """Token ids ``(B, S)`` -> logits ``(B, S, V)`` (float32)."""
    x, _ = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("head"):
        return llama_mod._head_logits(params, x, cfg)


def loss_fn(params, tokens, targets, cfg: SmallThinkerConfig, *, mesh=None,
            seq_axis: Optional[str] = None, attn_impl: str = "auto"):
    """``(loss, {"moe": counts})``: mean next-token cross-entropy over the
    vocabulary held, and the step's routing counts.  No auxiliary term."""
    if seq_axis is not None:
        raise ValueError("smallthinker has no sequence-parallel path")
    x, moe = _forward_hidden(
        params, tokens, cfg, mesh=mesh, attn_impl=attn_impl
    )
    return _head_ce(params, x, targets, cfg), {"moe": moe}
