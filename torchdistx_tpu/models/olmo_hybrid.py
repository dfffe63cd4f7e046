"""Olmo-Hybrid family (allenai/Olmo-Hybrid-7B, ``model_type:
olmo_hybrid``): gated delta-rule linear attention (Gated DeltaNet,
arXiv:2412.06464) and full softmax attention with no position term mixed
by layer, OLMo 3's post-norm block, a dense SwiGLU feed-forward after every
mixer — the training path.

No bias anywhere; every RMS norm computes in float32.  ``x0 = embed[ids]``,
then per layer, with NO norm before a sub-block::

    h = x + rmsnorm(mixer(x), post_attn_norm)
    x = h + rmsnorm((silu(h W_gate) * (h W_up)) W_down, post_ff_norm)

* ``full_attention``: ``q = rmsnorm(x W_q, q_norm)``, ``k = rmsnorm(x W_k,
  k_norm)``, each ONE norm over the whole projection (OLMo 3's, not one a
  head), ``v = x W_v``; ``n_heads`` heads on as many key/value heads,
  causal softmax at ``head_dim**-0.5`` with no rotary or other position
  term, through :func:`~torchdistx_tpu.ops.attention.attention`; ``W_o``.
* ``linear_attention`` (FLA's ``GatedDeltaNet``): ``q, k, v =
  silu(conv(x W_{q,k,v}))``, each a causal depthwise convolution of
  ``conv_taps`` taps (``jamba._conv``, no bias); per head ``q =
  l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)``; ``beta = 2 sigmoid(x W_b)``
  (``allow_neg_eigval``; ``sigmoid`` without it); ``g = -exp(A_log)
  softplus(x W_a + dt_bias)``;
  :func:`~torchdistx_tpu.ops.delta_rule.gated_delta_rule`; then ``(rmsnorm(o,
  o_norm) * silu(x W_z)) W_o``, the norm over ``d_v`` with one weight the
  heads share.

After the last layer the final norm and the untied head, in blocks of
rows (``_common.blocked_head_ce``).

A layer's kind is STATIC.  Two stacks, ``linear_layers`` and
``full_layers``, each ``(L_kind, ...)`` in layer order.  The layers run as
one scan over the whole periods of ``layer_types``, the period's layers one
after another in the body, each indexing its own kind's slice of the
period (the layout of ``minicpm_sala._run_layers``), and the layers after
the last whole period unrolled.  Each layer is rematerialised with
``ops.remat.REMAT_POLICY``: it keeps its input and, a full layer,
``flash_out`` and ``flash_lse``, a linear layer the delta rule's
``delta_out`` and ``delta_starts``.

Scopes: ``embed``; ``attn`` (full layers) with ``proj_in``, ``qk_norm``,
``proj_out``, ``post_norm``; ``gdn`` (linear layers) with ``proj_in``,
``conv``, ``l2norm``, ``gates`` (beta and g), ``delta``, ``out_norm``,
``proj_out``, ``post_norm``; ``mlp``; ``head``; ``stack``.  Counters: the
ops' own (``attention.*``, ``delta_rule*``).

Not here yet: a cache (no ``init_cache``: no serving path for the delta
rule's state beside pages), packed documents (the delta rule and the
convolutions know no segment), a sequence-parallel path.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention
from ..ops.delta_rule import gated_delta_rule
from ..ops.remat import REMAT_POLICY
from . import afmoe as afmoe_mod
from . import jamba as jamba_mod
from . import llama as llama_mod
from ._common import blocked_head_ce

__all__ = [
    "OlmoHybridConfig",
    "LINEAR",
    "FULL",
    "olmo_hybrid_test",
    "init_params",
    "abstract_params",
    "param_specs",
    "forward",
    "loss_fn",
    "num_params",
]

LINEAR, FULL = "linear_attention", "full_attention"

# The published layer_types (32 layers): linear x 3, then full, 8 times.
PUBLISHED_LAYERS = (LINEAR, LINEAR, LINEAR, FULL) * 8


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    n_layers: int = 32
    n_heads: int = 30  # the full layer's query heads = key/value heads
    head_dim: int = 128
    linear_heads: int = 30  # linear_num_key_heads = linear_num_value_heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_taps: int = 4
    allow_neg_eigval: bool = True
    ffn_dim: int = 11008
    layer_types: Optional[Tuple[str, ...]] = None  # None: as published
    norm_eps: float = 1e-6
    delta_chunk: int = 128
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        kinds = self.layer_types
        kinds = PUBLISHED_LAYERS if kinds is None else tuple(kinds)
        object.__setattr__(self, "layer_types", kinds)
        if len(kinds) != self.n_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers as {LINEAR!r} "
                f"or {FULL!r}, got {kinds}"
            )

    @property
    def n_full(self) -> int:
        return sum(k == FULL for k in self.layer_types)

    @property
    def n_linear(self) -> int:
        return self.n_layers - self.n_full


def olmo_hybrid_test() -> OlmoHybridConfig:
    """Two periods of linear, full (the published period's two kinds, one
    linear layer of its three): 4 heads of 16 (full), 4 linear heads of
    8 / 16, chunks of 16."""
    return OlmoHybridConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, head_dim=16,
        linear_heads=4, linear_key_dim=8, linear_value_dim=16, ffn_dim=96,
        layer_types=(LINEAR, FULL) * 2, delta_chunk=16,
        dtype=jnp.float32, remat=False,
    )


def _shapes(cfg: OlmoHybridConfig) -> dict:
    D, V, F = cfg.dim, cfg.vocab_size, cfg.ffn_dim
    Lf, Ll = cfg.n_full, cfg.n_linear
    H, hd = cfg.n_heads, cfg.head_dim
    Hl, dk, dv, K = (
        cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.conv_taps
    )

    def block(L):
        return {
            "post_attn_norm": (L, D), "post_ff_norm": (L, D),
            "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D),
        }

    return {
        "embed": {"weight": (V, D)},
        "linear_layers": {
            "wq": (Ll, D, Hl * dk), "wk": (Ll, D, Hl * dk),
            "wv": (Ll, D, Hl * dv), "wz": (Ll, D, Hl * dv),
            "wb": (Ll, D, Hl), "wa": (Ll, D, Hl), "wo": (Ll, Hl * dv, D),
            "conv_q": (Ll, K, Hl * dk), "conv_k": (Ll, K, Hl * dk),
            "conv_v": (Ll, K, Hl * dv), "A_log": (Ll, Hl), "dt_bias": (Ll, Hl),
            "o_norm": (Ll, dv), **block(Ll),
        },
        "full_layers": {
            "wq": (Lf, D, H * hd), "wk": (Lf, D, H * hd), "wv": (Lf, D, H * hd),
            "wo": (Lf, H * hd, D), "q_norm": (Lf, H * hd), "k_norm": (Lf, H * hd),
            **block(Lf),
        },
        "norm": {"weight": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def _is_shape(x):
    return isinstance(x, tuple)


def abstract_params(cfg: OlmoHybridConfig):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype), _shapes(cfg),
        is_leaf=_is_shape,
    )


def num_params(cfg: OlmoHybridConfig) -> int:
    return sum(
        math.prod(s) for s in jax.tree.leaves(_shapes(cfg), is_leaf=_is_shape)
    )


def param_specs(
    cfg: OlmoHybridConfig, *, tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
):
    """FSDP + Megatron-TP specs matching :func:`abstract_params`: column
    projections shard their out dim over ``tp``, row projections their in
    dim, the other large dim over ``fsdp``; norms, the convolutions and the
    gates' per-head leaves replicate."""
    col, row = P(None, fsdp, tp), P(None, tp, fsdp)
    block = {
        "post_attn_norm": P(), "post_ff_norm": P(),
        "w_gate": col, "w_up": col, "w_down": row,
    }
    return {
        "embed": {"weight": P(fsdp, tp)},
        "linear_layers": {
            "wq": col, "wk": col, "wv": col, "wz": col, "wb": P(None, fsdp, None),
            "wa": P(None, fsdp, None), "wo": row, "conv_q": P(), "conv_k": P(),
            "conv_v": P(), "A_log": P(), "dt_bias": P(), "o_norm": P(), **block,
        },
        "full_layers": {
            "wq": col, "wk": col, "wv": col, "wo": row, "q_norm": P(),
            "k_norm": P(), **block,
        },
        "norm": {"weight": P()},
        "lm_head": {"weight": P(fsdp, tp)},
    }


def init_params(key, cfg: OlmoHybridConfig):
    """N(0, 0.02) for every matrix and convolution, N(0, 1) for the
    embedding's rows (``nn.Embedding``'s own, as ``olmo_hybrid_torch``
    draws them), ones for norms, ``A_log = log U(0, 16)`` and ``dt_bias =
    1`` (``Qwen3NextPreTrainedModel._init_weights``); per-leaf ``fold_in``
    keys."""
    import zlib

    def leaf(path, shape):
        name = path[-1]
        if name.endswith("norm") or path[0] == "norm":
            return jnp.ones(shape, dtype=cfg.dtype)
        if name == "dt_bias":
            return jnp.ones(shape, dtype=cfg.dtype)
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        if name == "A_log":
            u = jax.random.uniform(leaf_key, shape, jnp.float32, 0.0, 16.0)
            return jnp.log(u).astype(cfg.dtype)
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


def _kernel_impl(attn_impl: str) -> str:
    return attn_impl if attn_impl in ("jnp", "pallas") else "auto"


def _l2norm(x, eps=1e-6):
    """FLA's (and ``Qwen3NextGatedDeltaNet``'s) ``x / sqrt(sum x^2 +
    eps)`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def _qk_l2norm(q, k):
    """Per head: ``q`` to unit length over ``sqrt(d_k)``, ``k`` to unit
    length, in q's dtype."""
    qn = _l2norm(q) / math.sqrt(q.shape[-1])
    return qn.astype(q.dtype), _l2norm(k).astype(k.dtype)


def _out_gate(o, z):
    """The gated norm's gate: ``o * silu(z)`` in float32, in o's dtype."""
    return (o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


def _qk_norm(q, k, lp, eps):
    """ONE RMS norm over the whole projection each (OLMo 3's)."""
    return (
        llama_mod._rmsnorm(q, lp["q_norm"], eps),
        llama_mod._rmsnorm(k, lp["k_norm"], eps),
    )


def _conv(u, w):
    """The causal depthwise convolution, no bias."""
    return jamba_mod._conv(u, w, jnp.zeros(u.shape[-1:], u.dtype))


def _gdn(x, lp, cfg, *, attn_impl):
    """``x (B, T, D)`` plus its Gated DeltaNet mixer's normed output."""
    b, s, _ = x.shape
    hl, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    with jax.named_scope("gdn"):
        with jax.named_scope("proj_in"):
            q, k, v, z = (x @ lp[w] for w in ("wq", "wk", "wv", "wz"))
        with jax.named_scope("conv"):
            q, k, v = (
                jax.nn.silu(_conv(u, lp[w]))
                for u, w in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v"))
            )
        with jax.named_scope("l2norm"):
            q, k = _qk_l2norm(q.reshape(b, s, hl, dk), k.reshape(b, s, hl, dk))
        with jax.named_scope("gates"):
            beta = jax.nn.sigmoid((x @ lp["wb"]).astype(jnp.float32))
            if cfg.allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
                (x @ lp["wa"]).astype(jnp.float32)
                + lp["dt_bias"].astype(jnp.float32)
            )
        with jax.named_scope("delta"):
            o = gated_delta_rule(
                q, k, v.reshape(b, s, hl, dv), g, beta, chunk=cfg.delta_chunk,
                impl=_kernel_impl(attn_impl),
            )
        with jax.named_scope("out_norm"):
            o = llama_mod._rmsnorm(o, lp["o_norm"], cfg.norm_eps)
            o = _out_gate(o, z.reshape(b, s, hl, dv))
        with jax.named_scope("proj_out"):
            y = o.reshape(b, s, hl * dv) @ lp["wo"]
        with jax.named_scope("post_norm"):
            return x + llama_mod._rmsnorm(y, lp["post_attn_norm"], cfg.norm_eps)


def _attn(x, lp, cfg, *, mesh, attn_impl):
    """``x (B, T, D)`` plus its full attention mixer's normed output."""
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("proj_in"):
            q, k, v = (x @ lp[w] for w in ("wq", "wk", "wv"))
        with jax.named_scope("qk_norm"):
            q, k = _qk_norm(q, k, lp, cfg.norm_eps)
        a = attention(
            *(t.reshape(b, s, h, hd) for t in (q, k, v)), causal=True,
            impl=attn_impl, mesh=mesh,
        )
        with jax.named_scope("proj_out"):
            y = a.reshape(b, s, h * hd) @ lp["wo"]
        with jax.named_scope("post_norm"):
            return x + llama_mod._rmsnorm(y, lp["post_attn_norm"], cfg.norm_eps)


def _mlp(x, lp, cfg):
    with jax.named_scope("mlp"):
        y = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return x + llama_mod._rmsnorm(y, lp["post_ff_norm"], cfg.norm_eps)


def _build_block(cfg: OlmoHybridConfig, *, mesh=None, attn_impl="auto"):
    """``block(kind)``: a layer of that kind as ``x, lp -> x``."""

    def block_of(kind):
        def block(x, lp):
            if kind == FULL:
                x = _attn(x, lp, cfg, mesh=mesh, attn_impl=attn_impl)
            else:
                x = _gdn(x, lp, cfg, attn_impl=attn_impl)
            return _mlp(x, lp, cfg)

        if cfg.remat:
            return jax.checkpoint(block, policy=REMAT_POLICY)
        return block

    return block_of


_STACKS = {LINEAR: "linear_layers", FULL: "full_layers"}


def _run_layers(x, params, kinds, block_of):
    """``x`` through the layers of both stacks in the order ``kinds`` gives.

    One scan over the whole periods of ``kinds``: each stack reshaped to
    ``(periods, its layers a period, ...)``, the body holding a period's
    layers one after another, each taking the next slice of its own kind;
    the layers past the last whole period after it, unrolled."""
    n = len(kinds)
    p = afmoe_mod._period(kinds)
    whole = n // p
    blocks = [block_of(kind) for kind in kinds[:p]]

    def layers(x, stacks, order):
        seen = dict.fromkeys(_STACKS, 0)
        for block, kind in zip(blocks, order):
            lp = jax.tree.map(lambda a, j=seen[kind]: a[j], stacks[kind])
            seen[kind] += 1
            x = block(x, lp)
        return x

    per = {kind: sum(k == kind for k in kinds[:p]) for kind in _STACKS}
    periods = {
        kind: jax.tree.map(
            lambda a, m=per[kind]: a[: whole * m].reshape(whole, m, *a.shape[1:]),
            params[name],
        )
        for kind, name in _STACKS.items()
    }
    x, _ = jax.lax.scan(lambda x, st: (layers(x, st, kinds[:p]), None), x, periods)
    if n > whole * p:
        rest = {
            kind: jax.tree.map(lambda a, m=per[kind]: a[whole * m:], params[name])
            for kind, name in _STACKS.items()
        }
        x = layers(x, rest, kinds[whole * p:])
    return x


def _forward_hidden(params, tokens, cfg, *, mesh=None, attn_impl="auto"):
    """Embedding + the layers + the final norm: what the head reads."""
    x = llama_mod._embed(params, tokens, cfg)
    block_of = _build_block(cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("stack"):
        x = _run_layers(x, params, cfg.layer_types, block_of)
    with jax.named_scope("head"):
        return llama_mod._rmsnorm(x, params["norm"]["weight"], cfg.norm_eps)


def forward(params, tokens, cfg: OlmoHybridConfig, *, mesh=None,
            attn_impl: str = "auto"):
    """Token ids ``(B, S)`` -> logits ``(B, S, V)`` (float32)."""
    h = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("head"):
        return (h @ params["lm_head"]["weight"].astype(cfg.dtype)).astype(
            jnp.float32
        )


def loss_fn(params, tokens, targets, cfg: OlmoHybridConfig, *, mesh=None,
            seq_axis: Optional[str] = None, attn_impl: str = "auto"):
    """Mean next-token cross-entropy."""
    if seq_axis is not None:
        raise ValueError("olmo_hybrid has no sequence-parallel path")
    h = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("head"):
        return blocked_head_ce(
            h.reshape(-1, h.shape[-1]),
            params["lm_head"]["weight"].astype(cfg.dtype),
            targets.reshape(-1), vocab_major=False,
        )
