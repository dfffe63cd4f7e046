"""GPT-2 family, TPU-native (BASELINE config 3's model family).

Same design rules as the flagship (:mod:`torchdistx_tpu.models.llama`):
stacked layers + ``lax.scan``, bf16 matmuls / f32 reductions, ``(in, out)``
weight layout, sharding via :func:`param_specs`, remat.  GPT-2 specifics:
learned positional embeddings, pre-LN with biases, GELU MLP, standard MHA
(no GQA), logits tied to the token embedding.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention
from ..ops.remat import REMAT_POLICY

__all__ = [
    "GPT2Config",
    "gpt2_test",
    "gpt2_small",
    "gpt2_xl",
    "init_params",
    "abstract_params",
    "param_specs",
    "forward",
    "loss_fn",
    "num_params",
    "init_cache",
    "forward_cached",
    "forward_paged",
    "pp_pieces",
    "pp_value_and_grad",
]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    max_seq_len: int = 1024
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Training-forward layer-loop unroll; None = auto (see llama).
    layer_unroll: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def _unroll(self) -> int:
        if self.layer_unroll:
            return self.layer_unroll
        return self.n_layers if self.n_layers <= 32 else 1

    @property
    def ffn_dim(self) -> int:
        return 4 * self.dim


def gpt2_test() -> GPT2Config:
    return GPT2Config(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, max_seq_len=128,
        dtype=jnp.float32, remat=False,
    )


def gpt2_small() -> GPT2Config:
    return GPT2Config()


def gpt2_xl() -> GPT2Config:
    return GPT2Config(dim=1600, n_layers=48, n_heads=25, max_seq_len=1024)


def _shapes(cfg: GPT2Config) -> dict:
    L, D, F, V, S = (
        cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size, cfg.max_seq_len,
    )
    return {
        "wte": {"weight": (V, D)},
        "wpe": {"weight": (S, D)},
        "layers": {
            "ln_1": {"scale": (L, D), "bias": (L, D)},
            "attn_qkv": {"weight": (L, D, 3 * D), "bias": (L, 3 * D)},
            "attn_proj": {"weight": (L, D, D), "bias": (L, D)},
            "ln_2": {"scale": (L, D), "bias": (L, D)},
            "mlp_fc": {"weight": (L, D, F), "bias": (L, F)},
            "mlp_proj": {"weight": (L, F, D), "bias": (L, D)},
        },
        "ln_f": {"scale": (D,), "bias": (D,)},
    }


def abstract_params(cfg: GPT2Config):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype),
        _shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def param_specs(
    cfg: GPT2Config,
    *,
    tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
    pp: Optional[str] = None,
):
    """Megatron TP for GPT-2: qkv/fc column-parallel (out dim), proj
    row-parallel (in dim); embeddings sharded (vocab|seq over fsdp, model
    dim over tp); norms replicated; ``pp`` shards the layer dim into
    pipeline stages."""
    return {
        "wte": {"weight": P(fsdp, tp)},
        "wpe": {"weight": P(fsdp, tp)},
        "layers": {
            "ln_1": {"scale": P(pp), "bias": P(pp)},
            "attn_qkv": {"weight": P(pp, fsdp, tp), "bias": P(pp, tp)},
            "attn_proj": {"weight": P(pp, tp, fsdp), "bias": P(pp)},
            "ln_2": {"scale": P(pp), "bias": P(pp)},
            "mlp_fc": {"weight": P(pp, fsdp, tp), "bias": P(pp, tp)},
            "mlp_proj": {"weight": P(pp, tp, fsdp), "bias": P(pp)},
        },
        "ln_f": {"scale": P(), "bias": P()},
    }


def init_params(key, cfg: GPT2Config):
    """GPT-2 init: N(0, 0.02) weights/embeddings, residual projections
    scaled by 1/sqrt(2·n_layers), zeros biases, ones LN scales."""
    import zlib

    shapes = _shapes(cfg)
    resid_scaled = {"attn_proj", "mlp_proj"}

    def leaf(path, shape):
        name = path[-1]
        parent = path[-2] if len(path) > 1 else ""
        if name == "scale":
            return jnp.ones(shape, dtype=cfg.dtype)
        if name == "bias":
            return jnp.zeros(shape, dtype=cfg.dtype)
        std = 0.02
        if parent in resid_scaled:
            std = 0.02 / (2.0 * cfg.n_layers) ** 0.5
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        return (
            jax.random.normal(leaf_key, shape, dtype=jnp.float32) * std
        ).astype(cfg.dtype)

    def walk(tree, path=()):
        if isinstance(tree, tuple):
            return leaf(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(shapes)


def num_params(cfg: GPT2Config) -> int:
    total = 0
    for leaf in jax.tree.leaves(
        _shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    ):
        n = 1
        for s in leaf:
            n *= s
        total += n
    return total


def _layernorm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * scale.astype(x.dtype) + bias.astype(x.dtype)


# Shared by the unpipelined forward/loss and the 1F1B pieces — one
# definition of the embedding, the head, and the loss, so the paths
# cannot drift.


def _embed(params, tokens, cfg: GPT2Config):
    """wte[tokens] + wpe[:S] — ``params`` needs only ``wte``/``wpe``."""
    s = tokens.shape[1]
    with jax.named_scope("embed"):
        x = jnp.take(params["wte"]["weight"], tokens, axis=0).astype(
            cfg.dtype
        )
        return x + params["wpe"]["weight"][:s].astype(cfg.dtype)[None]


def _head(params, x, cfg: GPT2Config):
    """ln_f + tied-embedding logits in ``cfg.dtype`` — the ONE head
    definition; needs ``ln_f``/``wte``."""
    x = _layernorm(
        x, params["ln_f"]["scale"], params["ln_f"]["bias"], cfg.norm_eps
    )
    return x @ params["wte"]["weight"].astype(cfg.dtype).T


def _head_logits(params, x, cfg: GPT2Config):
    """:func:`_head` under the public f32-logits contract."""
    return _head(params, x, cfg).astype(jnp.float32)


def _ce(logits, targets):
    """Mean next-token CE in f32 from logits of any float dtype (see
    llama._ce: logsumexp form, upcast fused into the reduction)."""
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1
    )
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[
        ..., 0
    ].astype(jnp.float32)
    return (lse - tgt).mean()


def _head_ce(params, x, targets, cfg: GPT2Config):
    """Loss-path :func:`_head` + CE with ``cfg.dtype`` logits (see
    llama._head_ce; bitwise-identical to ``_ce(_head_logits(...))`` at
    float32)."""
    with jax.named_scope("head"):
        return _ce(_head(params, x, cfg), targets)


def _build_block(
    cfg: GPT2Config, *, mesh=None, seq_axis=None, attn_impl="auto"
):
    """One transformer block as ``block(x, lp) -> x`` over unstacked layer
    params — shared by :func:`forward` and the 1F1B pipeline pieces."""

    # attn/mlp named_scope regions, the names forward_paged uses: HLO
    # metadata only, so a profile's device time can be read by scope
    # (docs/observability.md, "Scopes inside the train step").
    def block(x, lp):
        bb, s = x.shape[0], x.shape[1]
        with jax.named_scope("attn"):
            with jax.named_scope("norm"):
                h = _layernorm(
                    x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], cfg.norm_eps
                )
            with jax.named_scope("proj_in"):
                qkv = h @ lp["attn_qkv"]["weight"] + lp["attn_qkv"][
                    "bias"
                ].astype(cfg.dtype)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(bb, s, cfg.n_heads, cfg.head_dim)
                k = k.reshape(bb, s, cfg.n_heads, cfg.head_dim)
                v = v.reshape(bb, s, cfg.n_heads, cfg.head_dim)
            attn = attention(
                q, k, v, causal=True, impl=attn_impl, mesh=mesh,
                seq_axis=seq_axis,
            ).reshape(bb, s, -1)
            with jax.named_scope("proj_out"):
                x = x + attn @ lp["attn_proj"]["weight"] + lp["attn_proj"][
                    "bias"
                ].astype(cfg.dtype)
        with jax.named_scope("mlp"):
            h = _layernorm(
                x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], cfg.norm_eps
            )
            h = jax.nn.gelu(
                h @ lp["mlp_fc"]["weight"]
                + lp["mlp_fc"]["bias"].astype(cfg.dtype)
            )
            x = x + h @ lp["mlp_proj"]["weight"] + lp["mlp_proj"][
                "bias"
            ].astype(cfg.dtype)
        return x

    return block


def forward(
    params,
    tokens,
    cfg: GPT2Config,
    *,
    mesh=None,
    seq_axis: Optional[str] = None,
    attn_impl: str = "auto",
    pp_axis: Optional[str] = None,
    n_microbatches: int = 1,
):
    """Token ids ``(B, S)`` → logits ``(B, S, V)`` (f32, tied embeddings)."""
    x = _forward_hidden(
        params, tokens, cfg, mesh=mesh, seq_axis=seq_axis,
        attn_impl=attn_impl, pp_axis=pp_axis,
        n_microbatches=n_microbatches,
    )
    return _head_logits(params, x, cfg)


def _forward_hidden(
    params,
    tokens,
    cfg: GPT2Config,
    *,
    mesh=None,
    seq_axis: Optional[str] = None,
    attn_impl: str = "auto",
    pp_axis: Optional[str] = None,
    n_microbatches: int = 1,
):
    """Embedding + blocks, no ln_f/head (see llama._forward_hidden)."""
    if pp_axis is not None:
        from ..ops.attention import resolve_stage_attn_impl

        attn_impl = resolve_stage_attn_impl(attn_impl)
    x = _embed(params, tokens, cfg)

    block = _build_block(
        cfg, mesh=mesh, seq_axis=seq_axis, attn_impl=attn_impl
    )
    body = (
        jax.checkpoint(block, policy=REMAT_POLICY) if cfg.remat else block
    )
    if pp_axis is not None:
        from ..parallel.pipeline import pipeline_forward

        x = pipeline_forward(
            x, params["layers"], body, mesh=mesh, axis=pp_axis,
            n_microbatches=n_microbatches,
        )
    else:
        # ``stack``: the scan's own work (a layer's weights sliced out,
        # the stacked gradients and residuals written in its transpose).
        with jax.named_scope("stack"):
            x, _ = jax.lax.scan(lambda h, lp: (body(h, lp), None), x,
                                params["layers"], unroll=cfg._unroll)
    return x


def init_cache(cfg: GPT2Config, batch: int, max_len: int):
    """Static-shape KV cache: ``(L, B, Smax, H, Dh)`` per k/v."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype=cfg.dtype),
        "v": jnp.zeros(shape, dtype=cfg.dtype),
    }


def forward_cached(params, tokens, cfg: GPT2Config, cache, pos):
    """Incremental forward (see :func:`llama.forward_cached`)."""
    from ..ops.attention import cached_attention

    b, t = tokens.shape
    x = jnp.take(params["wte"]["weight"], tokens, axis=0).astype(cfg.dtype)
    pos_ids = pos + jnp.arange(t)
    x = x + jnp.take(params["wpe"]["weight"], pos_ids, axis=0).astype(
        cfg.dtype
    )[None]

    def block(carry, layer):
        # Caches ride the carry, updated in place with a one-token slice
        # (scan xs/ys would copy the full per-layer cache every layer —
        # see llama.forward_cached).
        x, kc, vc = carry
        lp, i = layer
        h = _layernorm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], cfg.norm_eps)
        qkv = h @ lp["attn_qkv"]["weight"] + lp["attn_qkv"]["bias"].astype(
            cfg.dtype
        )
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = k.reshape(b, t, cfg.n_heads, cfg.head_dim)
        v = v.reshape(b, t, cfg.n_heads, cfg.head_dim)
        kc = jax.lax.dynamic_update_slice(kc, k[None], (i, 0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v[None], (i, 0, pos, 0, 0))
        attn = cached_attention(
            q,
            jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False),
            pos,
        ).reshape(b, t, -1)
        x = x + attn @ lp["attn_proj"]["weight"] + lp["attn_proj"][
            "bias"
        ].astype(cfg.dtype)
        h = _layernorm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], cfg.norm_eps)
        h = jax.nn.gelu(
            h @ lp["mlp_fc"]["weight"] + lp["mlp_fc"]["bias"].astype(cfg.dtype)
        )
        x = x + h @ lp["mlp_proj"]["weight"] + lp["mlp_proj"]["bias"].astype(
            cfg.dtype
        )
        return (x, kc, vc), None

    (x, new_k, new_v), _ = jax.lax.scan(
        block,
        (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return _head_logits(params, x, cfg), {"k": new_k, "v": new_v}


def forward_paged(params, tokens, cfg: GPT2Config, cache, block_tables,
                  positions):
    """``T`` tokens per slot against a paged KV cache — per-slot
    positions; ``T == 1`` decode, ``T > 1`` a chunked-prefill block
    (see :func:`llama.forward_paged`; GPT-2: learned positional embeds,
    pre-LN biases, no GQA)."""
    from ..ops.attention import paged_attention, paged_write_index

    b, t = tokens.shape
    pos_ids = positions[:, None] + jnp.arange(t)[None]
    x = jnp.take(params["wte"]["weight"], tokens, axis=0).astype(cfg.dtype)
    # jnp.take clamps out-of-range ids: a chunk's padding tail past
    # max_seq_len reads the last wpe row, and its K/V lands in trash.
    x = x + jnp.take(params["wpe"]["weight"], pos_ids, axis=0).astype(
        cfg.dtype
    )
    blk, off = paged_write_index(
        block_tables, pos_ids, cache["k"].shape[2]
    )

    # attn/mlp named_scope regions for profiler attribution (see
    # llama.forward_paged) — HLO metadata only, values unchanged.
    def block(carry, layer):
        x, kc, vc = carry
        lp, i = layer
        with jax.named_scope("attn"):
            h = _layernorm(
                x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], cfg.norm_eps
            )
            qkv = h @ lp["attn_qkv"]["weight"] + lp["attn_qkv"][
                "bias"
            ].astype(cfg.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
            k = k.reshape(b, t, cfg.n_heads, cfg.head_dim)
            v = v.reshape(b, t, cfg.n_heads, cfg.head_dim)
            kc = kc.at[i, blk, off].set(k)
            vc = vc.at[i, blk, off].set(v)
            attn = paged_attention(
                q,
                jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False),
                block_tables,
                positions,
            ).reshape(b, t, -1)
            x = x + attn @ lp["attn_proj"]["weight"] + lp["attn_proj"][
                "bias"
            ].astype(cfg.dtype)
        with jax.named_scope("mlp"):
            h = _layernorm(
                x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], cfg.norm_eps
            )
            h = jax.nn.gelu(
                h @ lp["mlp_fc"]["weight"]
                + lp["mlp_fc"]["bias"].astype(cfg.dtype)
            )
            x = x + h @ lp["mlp_proj"]["weight"] + lp["mlp_proj"][
                "bias"
            ].astype(cfg.dtype)
        return (x, kc, vc), None

    (x, new_k, new_v), _ = jax.lax.scan(
        block,
        (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return _head_logits(params, x, cfg), {"k": new_k, "v": new_v}


def loss_fn(
    params,
    tokens,
    targets,
    cfg: GPT2Config,
    *,
    mesh=None,
    seq_axis: Optional[str] = None,
    attn_impl: str = "auto",
    pp_axis: Optional[str] = None,
    n_microbatches: int = 1,
):
    x = _forward_hidden(
        params, tokens, cfg, mesh=mesh, seq_axis=seq_axis,
        attn_impl=attn_impl, pp_axis=pp_axis,
        n_microbatches=n_microbatches,
    )
    return _head_ce(params, x, targets, cfg)


# ---------------------------------------------------------------------------
# 1F1B pipeline pieces (see parallel.pipeline.pipeline_value_and_grad):
# wte+wpe embedding on stage 0, blocks pipelined, ln_f + tied-logits loss
# inside the last stage.


def pp_pieces(cfg: GPT2Config, *, mesh=None, attn_impl: str = "auto"):
    """``(embed_fn, block_fn, head_loss_fn)`` for the 1F1B schedule.

    Shares :func:`_embed` / :func:`_head_logits` / :func:`_ce` with the
    unpipelined forward/loss so the two paths cannot drift."""
    from ..ops.attention import resolve_stage_attn_impl

    impl = resolve_stage_attn_impl(attn_impl)
    block = _build_block(cfg, mesh=mesh, attn_impl=impl)
    body = (
        jax.checkpoint(block, policy=REMAT_POLICY) if cfg.remat else block
    )

    def embed_fn(ep, tokens_mb):
        return _embed(ep, tokens_mb, cfg)

    def head_loss_fn(hp, h, targets_mb):
        return _head_ce(hp, h, targets_mb, cfg)

    return embed_fn, body, head_loss_fn


def pp_value_and_grad(
    params,
    tokens,
    targets,
    cfg: GPT2Config,
    *,
    mesh,
    pp_axis: str = "pp",
    n_microbatches: int = 1,
    attn_impl: str = "auto",
):
    """``(loss, grads)`` via the 1F1B pipeline.

    The TIED token embedding rides the pipeline's ``shared_params``
    channel: stage 0's embed and the last stage's head both read it, and
    it is carried with ONE (V, D) f32 gradient accumulator — its total
    gradient is the (psum'd) sum of the two contributions, exactly what
    autodiff of the tied forward produces, at half the accumulator
    memory of duplicating it into both stages' params."""
    from ..parallel.pipeline import pipeline_value_and_grad

    embed_fn, block_fn, head_loss_fn = pp_pieces(
        cfg, mesh=mesh, attn_impl=attn_impl
    )

    def embed_sp(ep_, tokens_mb, sp_):
        return embed_fn({**ep_, **sp_}, tokens_mb)

    def head_loss_sp(hp_, h, targets_mb, sp_):
        return head_loss_fn({**hp_, **sp_}, h, targets_mb)

    ep = {"wpe": params["wpe"]}
    hp = {"ln_f": params["ln_f"]}
    sp = {"wte": params["wte"]}
    loss, (g_ep, g_lp, g_hp, g_sp) = pipeline_value_and_grad(
        ep, params["layers"], hp, tokens, targets,
        embed_sp, block_fn, head_loss_sp,
        mesh=mesh, axis=pp_axis, n_microbatches=n_microbatches,
        shared_params=sp,
    )
    grads = {
        "wte": g_sp["wte"],
        "wpe": g_ep["wpe"],
        "layers": g_lp,
        "ln_f": g_hp["ln_f"],
    }
    return loss, grads
