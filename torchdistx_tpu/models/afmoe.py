"""AFMoE family (the layer equations of the published ``modeling_afmoe.py``,
``model_type: afmoe``): window and full attention layers mixed, per-head
q/k norms, a sigmoid output gate, sandwich norms, sigmoid-routed experts
beside a shared one — the training path.

No bias anywhere; every RMS norm computes in float32.  ``x0 = embed[ids] *
sqrt(dim)`` (``embed_scale``), then per layer ``i``::

    h = rmsnorm(x, attn_norm)
    q = rmsnorm((h W_q) per head, q_norm)      # weights of width head_dim,
    k = rmsnorm((h W_k) per head, k_norm)      #   shared by the heads
    v = (h W_v) per head;   g = h W_g          # the gate, from the SAME h
    layer_types[i] == "sliding_attention":
        q, k = rope(q, k)                      # half-split, all head_dim
        key j visible to query t  iff  0 <= t - j < window
    "full_attention":                          # NO position term at all
        key j visible to query t  iff  j <= t
    a = softmax(q k^T / sqrt(head_dim)) v      # query head n on kv head n // G
    x = x + rmsnorm((a * sigmoid(g)) W_o, post_attn_norm)
    h = rmsnorm(x, mlp_norm)
    m = SwiGLU(h)                              # the first n_dense_layers
      = SwiGLU_shared(h) + routed(h)           # the others
    x = x + rmsnorm(m, post_mlp_norm)

and ``logits = rmsnorm(x, norm) W_head`` (untied).  ``routed`` is
:func:`~torchdistx_tpu.models.deepseek_v3.moe_block` CALLED, not copied:
sigmoid scores, selection on score + bias (``router_bias``, the published
``expert_bias``: a buffer of zeros whose gradient is zero), weights
normalised over the chosen and scaled.  The layer is told which experts it
holds (``n_experts_held`` / ``first_expert_held``; the router stays
``n_experts`` wide); what absent experts would add is left out.

A layer's kind is STATIC.  Two stacks, ``dense_layers`` and ``moe_layers``,
each ``(L, ...)`` in layer order (window and full layers have the same
parameters: rope has none).  A stack's kinds repeat with some period ``p``;
it runs as one scan over its whole periods, ``(L // p, p, ...)`` by a free
reshape, whose body holds the ``p`` layers one after another, each with the
kernel its kind names, and any layers left over after them: no scan ever
chooses a kernel at run time.  Each layer is rematerialised with
``ops.remat.REMAT_POLICY`` (the flash kernels, banded or not, name
``flash_out`` and ``flash_lse``).  ``loss_fn`` returns ``(loss, aux)``
(``LOSS_HAS_AUX``) with ``aux["moe"]`` the step's routing counts.

Scopes: ``embed``; ``attn`` with ``qk_norm``, ``rope`` (window layers
only), ``gate`` under it and the post-norm directly in it; ``mlp`` (dense
layers) or ``moe`` (``router``, ``dispatch``, ``experts``, ``combine``,
``shared`` from the shared code) with their norms; ``head``.  Counters:
``moe.experts_held``, ``moe.experts_total``, and attention's own
(``attention.flash_window{window=}``, ``attention.window_kv_blocks``).

Not here yet: a cache (window and full layers in one page pool), packed
documents through a band, the window under ring attention.
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
from . import deepseek_v3 as deepseek_mod
from . import llama as llama_mod

__all__ = [
    "AfmoeConfig",
    "LOSS_HAS_AUX",
    "afmoe_test",
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

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    dim: int = 2048
    n_dense_layers: int = 2
    n_moe_layers: int = 30
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    ffn_dim: int = 6144  # the dense layers' feed-forward
    expert_dim: int = 1024  # one routed expert
    shared_dim: int = 1024  # the shared experts as one feed-forward
    n_experts: int = 128  # the router's width
    experts_per_token: int = 8
    routed_scale: float = 2.826
    # The share held here: all experts unless told otherwise.
    n_experts_held: Optional[int] = None
    first_expert_held: int = 0
    window: int = 2048
    # One kind a layer; None: every fourth layer full, as published.
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    embed_scale: bool = True  # ``mup_enabled``: embeddings times sqrt(dim)
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        kinds = self.layer_types
        if kinds is None:
            kinds = (
                FULL if (i + 1) % 4 == 0 else WINDOW
                for i in range(self.n_layers)
            )
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

    @property
    def n_layers(self) -> int:
        return self.n_dense_layers + self.n_moe_layers


def afmoe_test() -> AfmoeConfig:
    """One dense window layer, then window, window, full expert layers."""
    return AfmoeConfig(
        vocab_size=256, dim=64, n_dense_layers=1, n_moe_layers=3, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_dim=96, expert_dim=32, shared_dim=32,
        n_experts=8, experts_per_token=2, window=24,
        layer_types=(WINDOW, WINDOW, WINDOW, FULL), dtype=jnp.float32,
        remat=False,
    )


def _attn_shapes(cfg, n):
    D, H, Hkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "attn_norm": (n, D),
        "wq": (n, D, H * hd), "wk": (n, D, Hkv * hd), "wv": (n, D, Hkv * hd),
        "wg": (n, D, H * hd), "wo": (n, H * hd, D),
        "q_norm": (n, hd), "k_norm": (n, hd),
        "post_attn_norm": (n, D), "mlp_norm": (n, D), "post_mlp_norm": (n, D),
    }


def _shapes(cfg: AfmoeConfig) -> dict:
    D, V = cfg.dim, cfg.vocab_size
    Ld, Lm, E, Eh = cfg.n_dense_layers, cfg.n_moe_layers, cfg.n_experts, cfg.held
    F, Fe, Fs = cfg.ffn_dim, cfg.expert_dim, cfg.shared_dim
    return {
        "embed": {"weight": (V, D)},
        "dense_layers": {
            **_attn_shapes(cfg, Ld),
            "w_gate": (Ld, D, F), "w_up": (Ld, D, F), "w_down": (Ld, F, D),
        },
        "moe_layers": {
            **_attn_shapes(cfg, Lm),
            "router": (Lm, D, E), "router_bias": (Lm, E),
            "e_gate": (Lm, Eh, D, Fe), "e_up": (Lm, Eh, D, Fe),
            "e_down": (Lm, Eh, Fe, D),
            "s_gate": (Lm, D, Fs), "s_up": (Lm, D, Fs), "s_down": (Lm, Fs, D),
        },
        "norm": {"weight": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def _is_shape(x):
    return isinstance(x, tuple)


def abstract_params(cfg: AfmoeConfig):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype), _shapes(cfg),
        is_leaf=_is_shape,
    )


def num_params(cfg: AfmoeConfig) -> int:
    return sum(
        math.prod(s) for s in jax.tree.leaves(_shapes(cfg), is_leaf=_is_shape)
    )


def param_specs(
    cfg: AfmoeConfig, *, tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
):
    """FSDP + Megatron-TP specs matching :func:`abstract_params`: column
    projections (the gate's among them) shard their out dim over ``tp``,
    row projections their in dim, the other large dim over ``fsdp``; norms,
    router and bias replicate.  The held experts are NOT spread over a mesh
    axis: a chip of an expert-parallel deployment runs this program with
    its own ``first_expert_held``."""
    col, row = P(None, fsdp, tp), P(None, tp, fsdp)
    attn = {
        "attn_norm": P(), "wq": col, "wk": col, "wv": col, "wg": col,
        "wo": row, "q_norm": P(), "k_norm": P(), "post_attn_norm": P(),
        "mlp_norm": P(), "post_mlp_norm": P(),
    }
    return {
        "embed": {"weight": P(fsdp, tp)},
        "dense_layers": {**attn, "w_gate": col, "w_up": col, "w_down": row},
        "moe_layers": {
            **attn, "router": P(), "router_bias": P(),
            "e_gate": P(None, None, fsdp, tp), "e_up": P(None, None, fsdp, tp),
            "e_down": P(None, None, tp, fsdp),
            "s_gate": col, "s_up": col, "s_down": row,
        },
        "norm": {"weight": P()},
        "lm_head": {"weight": P(fsdp, tp)},
    }


def init_params(key, cfg: AfmoeConfig):
    """N(0, 0.02) for every matrix (the router's among them), ones for
    norms, zeros for the selection bias; per-leaf ``fold_in`` keys."""
    import zlib

    def leaf(path, shape):
        name = path[-1]
        if name.endswith("norm") or path[0] == "norm":
            return jnp.ones(shape, dtype=cfg.dtype)
        if name == "router_bias":
            return jnp.zeros(shape, dtype=cfg.dtype)
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        return (
            jax.random.normal(leaf_key, shape, dtype=jnp.float32) * 0.02
        ).astype(cfg.dtype)

    def walk(tree, path=()):
        if _is_shape(tree):
            return leaf(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(_shapes(cfg))


# ---------------------------------------------------------------------------
# Forward


def _gated(a, g):
    """The attention output times the sigmoid of the layer's gate."""
    return a * jax.nn.sigmoid(g)


def _attn(x, lp, cfg: AfmoeConfig, kind, *, mesh, attn_impl):
    b, s, _ = x.shape
    H, Hkv, hd, eps = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.norm_eps
    with jax.named_scope("attn"):
        with jax.named_scope("norm"):
            h = llama_mod._rmsnorm(x, lp["attn_norm"], eps)
        with jax.named_scope("proj_in"):
            q = (h @ lp["wq"]).reshape(b, s, H, hd)
            k = (h @ lp["wk"]).reshape(b, s, Hkv, hd)
            v = (h @ lp["wv"]).reshape(b, s, Hkv, hd)
        with jax.named_scope("qk_norm"):
            q = llama_mod._rmsnorm(q, lp["q_norm"], eps)
            k = llama_mod._rmsnorm(k, lp["k_norm"], eps)
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
        with jax.named_scope("gate"):
            a = _gated(a.reshape(b, s, H * hd), h @ lp["wg"])
        # with the sandwich's second norm, which sits before the add
        with jax.named_scope("proj_out"):
            return x + llama_mod._rmsnorm(
                a @ lp["wo"], lp["post_attn_norm"], eps
            )


def _build_blocks(cfg: AfmoeConfig, *, mesh=None, attn_impl="auto"):
    """``dense(kind)`` and ``moe(kind)``: a layer of that kind as ``x, lp
    -> (x, stats)``, with ``stats`` None in a dense layer and in an expert
    layer (assignments to held experts, busiest held expert over their
    mean, row chunks the routed layer ran)."""
    eps = cfg.norm_eps

    def wrap(block):
        if cfg.remat:
            return jax.checkpoint(block, policy=REMAT_POLICY)
        return block

    def dense(kind):
        def block(x, lp):
            x = _attn(x, lp, cfg, kind, mesh=mesh, attn_impl=attn_impl)
            with jax.named_scope("mlp"):
                h = llama_mod._rmsnorm(x, lp["mlp_norm"], eps)
                m = deepseek_mod._swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
                return x + llama_mod._rmsnorm(m, lp["post_mlp_norm"], eps), None

        return wrap(block)

    def moe(kind):
        def block(x, lp):
            x = _attn(x, lp, cfg, kind, mesh=mesh, attn_impl=attn_impl)
            with jax.named_scope("moe"):
                h = llama_mod._rmsnorm(x, lp["mlp_norm"], eps)
            m, stats = deepseek_mod.moe_block(h, lp, cfg)
            with jax.named_scope("moe"):
                x = x + llama_mod._rmsnorm(m, lp["post_mlp_norm"], eps)
            return x, (
                stats["local_assignments"], stats["load_max_over_mean"],
                stats["row_chunks"],
            )

        return wrap(block)

    return dense, moe


def _period(kinds) -> int:
    """The shortest ``p`` with ``kinds[i] == kinds[i % p]`` throughout."""
    n = len(kinds)
    return next(
        p for p in range(1, n + 1)
        if all(kinds[i] == kinds[i % p] for i in range(n))
    )


def _run_stack(x, stack, kinds, block_of):
    """``x`` through the layers of ``stack`` (leaves ``(L, ...)``), layer
    ``i`` by ``block_of(kinds[i])`` -> ``(x, stats)`` with each of a
    layer's stats stacked ``(L,)`` (None where the blocks give none).

    One scan over the whole periods of ``kinds``; its body, and what is
    left over after the last whole period, hold their layers one after
    another, so every layer's kind is static."""
    n = len(kinds)
    if not n:
        return x, None
    p = _period(kinds)
    blocks = [block_of(kind) for kind in kinds[:p]]
    whole = n // p

    def layers(x, lps):
        stats = []
        for j, block in enumerate(blocks[: jax.tree.leaves(lps)[0].shape[0]]):
            x, st = block(x, jax.tree.map(lambda a: a[j], lps))
            stats.append(st)
        if stats[0] is None:
            return x, None
        return x, jax.tree.map(lambda *s: jnp.stack(s), *stats)

    periods = jax.tree.map(
        lambda a: (a if n == whole * p else a[: whole * p]).reshape(
            whole, p, *a.shape[1:]
        ),
        stack,
    )
    x, stats = jax.lax.scan(layers, x, periods)
    if stats is not None:
        stats = jax.tree.map(lambda a: a.reshape(-1), stats)
    if n > whole * p:
        # The layers past the last whole period (a copy of their weights:
        # a stack whose kinds end on a period's boundary has none).
        x, tail = layers(x, jax.tree.map(lambda a: a[whole * p:], stack))
        if stats is not None:
            stats = jax.tree.map(
                lambda a, t: jnp.concatenate([a, t]), stats, tail
            )
    return x, stats


def _forward_hidden(params, tokens, cfg, *, mesh=None, attn_impl="auto"):
    """Embedding + both stacks -> ``(x, moe)`` with ``moe`` the step's
    routing counts (device scalars)."""
    _telemetry.counter("moe.experts_held").add(cfg.held)
    _telemetry.counter("moe.experts_total").add(cfg.n_experts)
    x = llama_mod._embed(params, tokens, cfg)
    if cfg.embed_scale:
        with jax.named_scope("embed"):
            x = x * (cfg.dim ** 0.5)
    dense, moe = _build_blocks(cfg, mesh=mesh, attn_impl=attn_impl)
    kinds, nd = cfg.layer_types, cfg.n_dense_layers
    # ``stack``: the periods' reshape, the scans' own work and each
    # layer's weights indexed out of its period; the blocks' scopes are
    # innermost.
    with jax.named_scope("stack"):
        x, _ = _run_stack(x, params["dense_layers"], kinds[:nd], dense)
        x, (assigned, load, chunks) = _run_stack(
            x, params["moe_layers"], kinds[nd:], moe
        )
    return x, {
        "local_assignments": assigned.sum(),
        "load_max_over_mean": load.mean(),
        "row_chunks": chunks.sum(),
    }


def forward(params, tokens, cfg: AfmoeConfig, *, mesh=None,
            attn_impl: str = "auto"):
    """Token ids ``(B, S)`` -> logits ``(B, S, V)`` (float32)."""
    x, _ = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("head"):
        return llama_mod._head_logits(params, x, cfg)


def loss_fn(params, tokens, targets, cfg: AfmoeConfig, *, mesh=None,
            seq_axis: Optional[str] = None, attn_impl: str = "auto"):
    """``(loss, {"moe": counts})``: mean next-token cross-entropy over the
    vocabulary held, and the step's routing counts.  No auxiliary term."""
    if seq_axis is not None:
        raise ValueError("afmoe has no sequence-parallel path")
    x, moe = _forward_hidden(
        params, tokens, cfg, mesh=mesh, attn_impl=attn_impl
    )
    return llama_mod._head_ce(params, x, targets, cfg), {"moe": moe}
