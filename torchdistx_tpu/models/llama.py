"""Llama-2-family decoder, TPU-native — the flagship model of this framework.

Pure-functional JAX implementation designed for the MXU and XLA's SPMD
partitioner, not a port of any torch module:

* **Stacked layers + ``lax.scan``** — all transformer blocks live in one
  pytree with a leading ``(n_layers, ...)`` dim, scanned over.  Compile time
  is O(1) in depth and XLA pipelines the layer loop.
* **bfloat16 compute, float32 softmax/norm/loss** — matmuls hit the MXU in
  bf16; numerically sensitive reductions run in f32.
* **Weights stored ``(in, out)``** so every projection is a plain ``x @ w``
  einsum that XLA tiles onto the 128×128 systolic array.
* **GQA** (``n_kv_heads <= n_heads``) and **RoPE** as in Llama-2/3.
* **Sharding by spec, not by code**: :func:`param_specs` emits a
  ``PartitionSpec`` pytree (Megatron-style TP + ZeRO-style FSDP dims);
  the forward is sharding-agnostic and XLA inserts the collectives.
* **Selective remat**: ``cfg.remat`` wraps the scanned block in
  ``jax.checkpoint`` with ``ops.remat.REMAT_POLICY``.  Saved per
  layer: the block's input and, where the Pallas flash kernel runs, its
  output ``flash_out`` (B, S, H*D) and log-sum-exp ``flash_lse`` (B, H, S)
  — B*S*H*(D*itemsize + 4) bytes.  Recomputed in the backward pass: the
  norms, the projections (q, k, v for the backward kernels) and the MLP,
  never the forward kernel.

Capability parity note: the reference's BASELINE configs name Llama-2-7B/70B
as deferred-init workloads (BASELINE.md configs 4-5); this module provides
the native training-side model those workloads feed into, plus
:func:`abstract_params` / :func:`init_sharded` — the JAX-native
shard-then-materialize flow (inspect shapes with zero allocation, then
compile init with sharded outputs so every shard is generated on its own
device; cf. /root/reference/docs/src/deferred_init.rst:17-44).
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
    "LlamaConfig",
    "llama_test",
    "llama_tiny",
    "llama_7b",
    "llama_70b",
    "init_params",
    "abstract_params",
    "init_sharded",
    "param_specs",
    "forward",
    "loss_fn",
    "num_params",
    "init_cache",
    "forward_cached",
    "forward_paged",
    "prep_decode",
    "pp_pieces",
    "pp_value_and_grad",
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Layer-loop unroll for the TRAINING forward: None = auto (full
    # unroll up to 32 layers — measured 20% faster fwd+bwd than the
    # rolled scan at 16 layers on v5e: XLA schedules/overlaps across
    # layer boundaries; partial unroll is WORSE than either extreme).
    # Beyond the auto bound the rolled scan keeps compile time O(1) in
    # depth.  The decode path always scans (measured: unroll loses).
    layer_unroll: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def _unroll(self) -> int:
        if self.layer_unroll:
            return self.layer_unroll
        return self.n_layers if self.n_layers <= 32 else 1


def llama_test() -> LlamaConfig:
    """CI-sized config: big enough to exercise GQA/scan/sharding."""
    return LlamaConfig(
        vocab_size=256,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq_len=128,
        dtype=jnp.float32,
        remat=False,
    )


def llama_tiny() -> LlamaConfig:
    """~15M params — single-chip smoke/bench scale."""
    return LlamaConfig(
        vocab_size=32000,
        dim=256,
        n_layers=4,
        n_heads=8,
        n_kv_heads=8,
        ffn_dim=688,
        max_seq_len=2048,
    )


def llama_7b() -> LlamaConfig:
    return LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=32,
        ffn_dim=11008, max_seq_len=4096,
    )


def llama_70b() -> LlamaConfig:
    return LlamaConfig(
        vocab_size=32000, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        ffn_dim=28672, max_seq_len=4096,
    )


# ---------------------------------------------------------------------------
# Parameters


def _shapes(cfg: LlamaConfig) -> dict:
    L, D, F, V = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size
    Hq = cfg.n_heads * cfg.head_dim
    Hkv = cfg.n_kv_heads * cfg.head_dim
    return {
        "embed": {"weight": (V, D)},
        "layers": {
            "attn_norm": (L, D),
            "wq": (L, D, Hq),
            "wk": (L, D, Hkv),
            "wv": (L, D, Hkv),
            "wo": (L, Hq, D),
            "mlp_norm": (L, D),
            "w_gate": (L, D, F),
            "w_up": (L, D, F),
            "w_down": (L, F, D),
        },
        "norm": {"weight": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def abstract_params(cfg: LlamaConfig):
    """Shape/dtype-only parameter pytree — the fake-tensor analog for the
    native model path (zero allocation; inspect then shard then init)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype),
        _shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def param_specs(
    cfg: LlamaConfig,
    *,
    tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
    pp: Optional[str] = None,
):
    """Megatron-TP + FSDP partition specs matching :func:`abstract_params`.

    Column-parallel projections (wq/wk/wv/w_gate/w_up) shard their *out* dim
    over ``tp``; row-parallel (wo/w_down) shard their *in* dim, so the pair
    needs exactly one ``psum`` per block (the classic Megatron layout).  The
    other large dim shards over ``fsdp`` (ZeRO-3).  Norms replicate.
    ``pp`` (if given) shards the stacked layer dim into pipeline stages.
    """
    return {
        "embed": {"weight": P(fsdp, tp)},
        "layers": {
            "attn_norm": P(pp),
            "wq": P(pp, fsdp, tp),
            "wk": P(pp, fsdp, tp),
            "wv": P(pp, fsdp, tp),
            "wo": P(pp, tp, fsdp),
            "mlp_norm": P(pp),
            "w_gate": P(pp, fsdp, tp),
            "w_up": P(pp, fsdp, tp),
            "w_down": P(pp, tp, fsdp),
        },
        "norm": {"weight": P()},
        "lm_head": {"weight": P(fsdp, tp)},
    }


def init_params(key, cfg: LlamaConfig):
    """Initialize parameters (host-order-independent: per-leaf fold_in keys).

    Scaled-normal init as in Llama: N(0, 0.02) for projections/embeddings,
    ones for norms; the down/out projections use the depth-scaled std
    0.02/sqrt(2*n_layers) (GPT-2/Llama residual-stream scaling).
    """
    import zlib

    shapes = _shapes(cfg)
    resid_scaled = {"wo", "w_down"}

    def leaf(path, shape):
        name = path[-1]
        if name in ("attn_norm", "mlp_norm") or path[0] == "norm":
            return jnp.ones(shape, dtype=cfg.dtype)
        std = 0.02
        if name in resid_scaled:
            std = 0.02 / (2.0 * cfg.n_layers) ** 0.5
        # crc32, not hash(): Python's str hash is salted per process, which
        # would make init non-deterministic across restarts and trace
        # *different* programs on different hosts.
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        return (jax.random.normal(leaf_key, shape, dtype=jnp.float32) * std).astype(
            cfg.dtype
        )

    def walk(tree, path=()):
        if isinstance(tree, tuple):
            return leaf(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(shapes)


def init_sharded(key, cfg: LlamaConfig, mesh, *, tp="tp", fsdp="fsdp"):
    """Shard-then-materialize, native: compile init with sharded outputs so
    XLA generates each parameter shard directly on its owning device — no
    full tensor ever exists on any single host/chip (the north-star flow of
    BASELINE.md; the torch-module analog is
    :func:`torchdistx_tpu.materialize.materialize_module_jax`)."""
    from ..parallel.sharding import fit_shardings

    specs = param_specs(cfg, tp=tp, fsdp=fsdp)
    shardings = fit_shardings(specs, abstract_params(cfg), mesh)
    fn = jax.jit(partial(init_params, cfg=cfg), out_shardings=shardings)
    return fn(key)


def num_params(cfg: LlamaConfig) -> int:
    total = 0
    for leaf in jax.tree.leaves(
        _shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    ):
        n = 1
        for s in leaf:
            n *= s
        total += n
    return total


# ---------------------------------------------------------------------------
# Forward


def _rmsnorm(x, weight, eps):
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * weight.astype(x.dtype)


# Shared by the unpipelined forward/loss and the 1F1B pieces — one
# definition of the embedding, the head and the loss, so the paths cannot
# drift.


def _embed(params, tokens, cfg: LlamaConfig):
    """embed[tokens] in ``cfg.dtype`` — ``params`` needs only ``embed``."""
    with jax.named_scope("embed"):
        return jnp.take(params["embed"]["weight"], tokens, axis=0).astype(
            cfg.dtype
        )


def _head(params, x, cfg: LlamaConfig):
    """Final norm + lm_head in ``cfg.dtype`` — the ONE head definition;
    needs ``norm``/``lm_head``."""
    x = _rmsnorm(x, params["norm"]["weight"], cfg.norm_eps)
    return x @ params["lm_head"]["weight"].astype(cfg.dtype)


def _head_logits(params, x, cfg: LlamaConfig):
    """:func:`_head` under the public f32-logits contract."""
    return _head(params, x, cfg).astype(jnp.float32)


def _ce(logits, targets):
    """Mean next-token cross-entropy in f32, from logits of any float
    dtype.  logsumexp form, not log_softmax: the full (B, S, V) log-prob
    array never materializes (measured ~2% of the 350M train step), and
    the f32 upcast fuses into the reduction, so bf16 logits never
    materialize an f32 copy either."""
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1
    )
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[
        ..., 0
    ].astype(jnp.float32)
    return (lse - tgt).mean()


def _head_ce(params, x, targets, cfg: LlamaConfig):
    """Loss-path head + CE: :func:`_head`'s ``cfg.dtype`` logits feed
    :func:`_ce` directly (the training loss never materializes the
    (B, S, V) float32 logits that :func:`forward`'s public contract
    returns — at bf16 that halves the loss path's HBM traffic).
    Bitwise-identical to ``_ce(_head_logits(...))`` at float32."""
    with jax.named_scope("head"):
        return _ce(_head(params, x, cfg), targets)


def _rope_tables(positions, theta, half, dtype):
    """(cos, sin) of shape (B, S, 1, half) — position-only, so callers
    iterating layers (the decode scan) compute them ONCE per step."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :].astype(dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(dtype)
    return cos, sin


def _rope_apply(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def _rope(x, positions, theta):
    # x: (B, S, H, D). Rotate pairs (even, odd) halves as in Llama.
    return _rope_apply(
        x, *_rope_tables(positions, theta, x.shape[-1] // 2, x.dtype)
    )


def _build_block(
    cfg: LlamaConfig,
    *,
    positions=None,
    mesh=None,
    seq_axis=None,
    attn_impl="auto",
    pre_permuted=False,
):
    """One transformer block as ``block(x, lp) -> x`` over unstacked layer
    params — shared by :func:`forward` and the 1F1B pipeline pieces.
    ``positions=None`` derives contiguous positions from the input shape."""

    # attn/mlp named_scope regions, the names forward_paged uses: HLO
    # metadata only, so a profile's device time can be read by scope
    # (docs/observability.md, "Scopes inside the train step").
    def block(x, lp):
        bb, s = x.shape[0], x.shape[1]
        with jax.named_scope("attn"):
            with jax.named_scope("norm"):
                h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
            with jax.named_scope("proj_in"):
                q = (h @ lp["wq"]).reshape(bb, s, cfg.n_heads, cfg.head_dim)
                k = (h @ lp["wk"]).reshape(
                    bb, s, cfg.n_kv_heads, cfg.head_dim
                )
                v = (h @ lp["wv"]).reshape(
                    bb, s, cfg.n_kv_heads, cfg.head_dim
                )
            with jax.named_scope("rope"):
                pos = (
                    jnp.arange(s)[None] if positions is None else positions
                )
                q = _rope(q, pos, cfg.rope_theta)
                k = _rope(k, pos, cfg.rope_theta)
            attn = attention(
                q, k, v, causal=True, impl=attn_impl, mesh=mesh,
                seq_axis=seq_axis, pre_permuted=pre_permuted,
            )
            with jax.named_scope("proj_out"):
                x = x + attn.reshape(bb, s, -1) @ lp["wo"]
        with jax.named_scope("mlp"):
            h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
            gated = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
            x = x + gated @ lp["w_down"]
        return x

    return block


def forward(
    params,
    tokens,
    cfg: LlamaConfig,
    *,
    mesh=None,
    seq_axis: Optional[str] = None,
    attn_impl: str = "auto",
    pp_axis: Optional[str] = None,
    n_microbatches: int = 1,
    seq_layout: str = "contiguous",
):
    """Token ids ``(B, S)`` → logits ``(B, S, V)`` (float32).

    Sharding-agnostic: run it under ``jit`` with sharded params/tokens and
    XLA partitions it.  ``seq_axis`` switches attention to the ring
    implementation over that mesh axis (sequence/context parallelism for
    long sequences).  ``pp_axis`` runs the transformer blocks through the
    GPipe pipeline (:mod:`torchdistx_tpu.parallel.pipeline`) with
    ``n_microbatches`` microbatches (pp composes with tp/fsdp; use jnp or
    pallas attention inside the pipeline, not ring).

    ``seq_layout="zigzag"`` keeps the *whole model's* activations in the
    zigzag sequence order of the load-balanced causal ring schedule:
    tokens are permuted once at the embedding, RoPE uses the original
    per-token positions, every attention call runs the zigzag ring with
    no per-layer resharding, and the returned logits are in **zigzag
    order** — use :func:`loss_fn`'s matching ``seq_layout`` (it aligns
    the targets), or invert with
    ``parallel.ring_attention._zigzag_perm(s, sp)[1]``.  Requires
    ``seq_axis`` and no pipeline axis.
    """
    x = _forward_hidden(
        params, tokens, cfg, mesh=mesh, seq_axis=seq_axis,
        attn_impl=attn_impl, pp_axis=pp_axis,
        n_microbatches=n_microbatches, seq_layout=seq_layout,
    )
    return _head_logits(params, x, cfg)


def _forward_hidden(
    params,
    tokens,
    cfg: LlamaConfig,
    *,
    mesh=None,
    seq_axis: Optional[str] = None,
    attn_impl: str = "auto",
    pp_axis: Optional[str] = None,
    n_microbatches: int = 1,
    seq_layout: str = "contiguous",
):
    """The transformer body of :func:`forward`: embedding + blocks, no
    final norm/head — shared by :func:`forward` (f32 logits, the public
    contract) and :func:`loss_fn` (cfg.dtype logits via :func:`_head_ce`,
    half the loss-path HBM traffic at bf16)."""
    b, s = tokens.shape
    if seq_layout == "zigzag":
        if seq_axis is None or mesh is None:
            raise ValueError("seq_layout='zigzag' needs mesh= and seq_axis=")
        if pp_axis is not None:
            raise ValueError("seq_layout='zigzag' does not compose with pp")
        from ..parallel.ring_attention import _zigzag_perm

        perm, _ = _zigzag_perm(s, mesh.shape[seq_axis])
        tokens = tokens[:, perm]
        # RoPE sees each token's ORIGINAL position.
        positions = jnp.asarray(perm)[None]
        if attn_impl not in ("auto", "ring_zigzag"):
            # Zigzag-ordered activations are only meaningful to the zigzag
            # ring schedule; any other kernel would attend in permuted order.
            raise ValueError(
                f"attn_impl={attn_impl!r} is incompatible with "
                "seq_layout='zigzag' (requires 'auto' or 'ring_zigzag')"
            )
        attn_impl = "ring_zigzag"
        pre_permuted = True
    elif seq_layout == "contiguous":
        # (1, S): broadcasts over any (micro)batch size.
        positions = jnp.arange(s)[None]
        pre_permuted = False
    else:
        raise ValueError(f"unknown seq_layout: {seq_layout!r}")
    if pp_axis is not None:
        from ..ops.attention import resolve_stage_attn_impl

        attn_impl = resolve_stage_attn_impl(attn_impl)
    x = _embed(params, tokens, cfg)

    block = _build_block(
        cfg, positions=positions, mesh=mesh, seq_axis=seq_axis,
        attn_impl=attn_impl, pre_permuted=pre_permuted,
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


def init_cache(cfg: LlamaConfig, batch: int, max_len: int):
    """Static-shape KV cache: ``(L, B, Smax, Hkv, Dh)`` per k/v."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype=cfg.dtype),
        "v": jnp.zeros(shape, dtype=cfg.dtype),
    }


def prep_decode(params, cfg: LlamaConfig):
    """Decode-prepped params: qkv and gate/up projections pre-fused.

    A decode step is latency-bound on per-op overhead, not FLOPs — fusing
    ``wq``/``wk``/``wv`` into one ``(D, (Hq+2·Hkv)·Dh)`` matmul and
    ``w_gate``/``w_up`` into one ``(D, 2F)`` matmul cuts the per-layer
    matmul count from 7 to 4.  Called ONCE per generation (outside the
    token scan — :mod:`.generate` hoists it), so the concat cost is
    amortized over every decode step.  :func:`forward_cached` accepts
    either raw or prepped params.  Idempotent: prepped input is returned
    unchanged.
    """
    if "wqkv" in params["layers"]:
        return params
    lp = dict(params["layers"])
    lp["wqkv"] = jnp.concatenate([lp.pop("wq"), lp.pop("wk"), lp.pop("wv")],
                                 axis=-1)
    lp["wgu"] = jnp.concatenate([lp.pop("w_gate"), lp.pop("w_up")], axis=-1)
    return {**params, "layers": lp}


def forward_cached(params, tokens, cfg: LlamaConfig, cache, pos):
    """Incremental forward: ``tokens (B, T)`` at positions ``pos..pos+T-1``.

    Returns ``(logits (B, T, V) f32, new_cache)``.  One compiled program
    serves both prefill (T = prompt length) and decode (T = 1) — shapes are
    static, ``pos`` is a traced scalar.  ``params`` may be raw or
    :func:`prep_decode`-prepped.  Raw params are fused IN the call — fine
    for a one-shot prefill, but a caller jitting a per-token decode loop
    directly must hoist :func:`prep_decode` out of the loop (as
    :mod:`.generate` does) or pay the weight-fusion concat every step.

    The KV caches ride the layer scan as CARRY, updated in place by a
    one-token ``dynamic_update_slice`` — passing them as scan xs/ys would
    copy the full per-layer cache every layer every step (~2× the cache
    size in HBM traffic per decode step).
    """
    from ..ops.attention import cached_attention

    if "wqkv" not in params["layers"]:
        params = prep_decode(params, cfg)
    b, t = tokens.shape
    x = jnp.take(params["embed"]["weight"], tokens, axis=0).astype(cfg.dtype)
    positions = jnp.broadcast_to(pos + jnp.arange(t), (b, t))
    n_q = cfg.n_heads * cfg.head_dim
    n_kv = cfg.n_kv_heads * cfg.head_dim
    # Rope tables are position-only — computed ONCE per step here, not per
    # layer inside the scan.
    cos, sin = _rope_tables(
        positions, cfg.rope_theta, cfg.head_dim // 2, cfg.dtype
    )

    def block(carry, layer):
        x, kc, vc = carry
        lp, i = layer
        h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        qkv = h @ lp["wqkv"]
        q = qkv[..., :n_q].reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = qkv[..., n_q:n_q + n_kv].reshape(
            b, t, cfg.n_kv_heads, cfg.head_dim
        )
        v = qkv[..., n_q + n_kv:].reshape(
            b, t, cfg.n_kv_heads, cfg.head_dim
        )
        q = _rope_apply(q, cos, sin)
        k = _rope_apply(k, cos, sin)
        kc = jax.lax.dynamic_update_slice(kc, k[None], (i, 0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v[None], (i, 0, pos, 0, 0))
        attn = cached_attention(
            q,
            jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False),
            pos,
        )
        x = x + attn.reshape(b, t, -1) @ lp["wo"]
        h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        gu = h @ lp["wgu"]
        gated = jax.nn.silu(gu[..., : cfg.ffn_dim]) * gu[..., cfg.ffn_dim:]
        x = x + gated @ lp["w_down"]
        return (x, kc, vc), None

    (x, new_k, new_v), _ = jax.lax.scan(
        block,
        (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return _head_logits(params, x, cfg), {"k": new_k, "v": new_v}


def forward_paged(params, tokens, cfg: LlamaConfig, cache, block_tables,
                  positions):
    """``T`` tokens per slot against a block/paged KV cache (serving path).

    ``tokens (B, T)`` holds each slot's current tokens at its OWN
    positions ``positions[b] .. positions[b]+T-1`` — unlike
    :func:`forward_cached`, whose scalar ``pos`` forces every batch row
    to the same depth, so it cannot serve a continuously batched decode
    where slots admit and retire independently.  ``T == 1`` is the
    decode step; ``T > 1`` is a **chunked-prefill block**: the chunk's
    KV scatters into the slot's pages, then every chunk query attends
    the slot's full cached prefix — shared prefix-cache pages included —
    plus the chunk itself (causal).  ``cache`` is the paged pool
    ``{"k","v"}: (L, NB, bs, Hkv, Dh)`` and ``block_tables (B, M)`` maps
    slot-logical blocks to pages (see :mod:`torchdistx_tpu.serving`).

    Returns ``(logits (B, T, V) f32, new cache)``.  Same fused-weight layer
    scan as :func:`forward_cached` (prep_decode applies; caches ride the
    scan carry), with the slice write/read swapped for a page scatter and
    the block-table gather of :func:`ops.attention.paged_attention` —
    values match the contiguous path exactly.

    A position that has run past its table (``pos//bs >= M``) scatters
    into page 0 — the trash page the serving engine never hands out — so
    a retired-but-still-batched slot (or a prefill chunk's padding tail)
    can never corrupt a live slot's cache.
    """
    from ..ops.attention import paged_attention, paged_write_index

    if "wqkv" not in params["layers"]:
        params = prep_decode(params, cfg)
    b, t = tokens.shape
    x = jnp.take(params["embed"]["weight"], tokens, axis=0).astype(cfg.dtype)
    n_q = cfg.n_heads * cfg.head_dim
    n_kv = cfg.n_kv_heads * cfg.head_dim
    pos_bt = positions[:, None] + jnp.arange(t)[None]
    cos, sin = _rope_tables(
        pos_bt, cfg.rope_theta, cfg.head_dim // 2, cfg.dtype,
    )
    # (B, T) write steering: each token of the block lands in its slot's
    # own pages (pads past the table steer to trash).
    blk, off = paged_write_index(
        block_tables, pos_bt, cache["k"].shape[2]
    )

    # jax.named_scope regions (attn/mlp) label the HLO so a profiler
    # capture (telemetry.timeplane, docs/observability.md "Time plane")
    # attributes device time to model regions — metadata only, the
    # compiled computation (and token identity) is unchanged.
    def block(carry, layer):
        x, kc, vc = carry
        lp, i = layer
        with jax.named_scope("attn"):
            h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
            qkv = h @ lp["wqkv"]
            q = qkv[..., :n_q].reshape(b, t, cfg.n_heads, cfg.head_dim)
            k = qkv[..., n_q:n_q + n_kv].reshape(
                b, t, cfg.n_kv_heads, cfg.head_dim
            )
            v = qkv[..., n_q + n_kv:].reshape(
                b, t, cfg.n_kv_heads, cfg.head_dim
            )
            q = _rope_apply(q, cos, sin)
            k = _rope_apply(k, cos, sin)
            kc = kc.at[i, blk, off].set(k)
            vc = vc.at[i, blk, off].set(v)
            attn = paged_attention(
                q,
                jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False),
                block_tables,
                positions,
            )
            x = x + attn.reshape(b, t, -1) @ lp["wo"]
        with jax.named_scope("mlp"):
            h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
            gu = h @ lp["wgu"]
            gated = (
                jax.nn.silu(gu[..., : cfg.ffn_dim]) * gu[..., cfg.ffn_dim:]
            )
            x = x + gated @ lp["w_down"]
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
    cfg: LlamaConfig,
    *,
    mesh=None,
    seq_axis: Optional[str] = None,
    attn_impl: str = "auto",
    pp_axis: Optional[str] = None,
    n_microbatches: int = 1,
    seq_layout: str = "contiguous",
):
    """Mean next-token cross-entropy (float32).

    ``seq_layout="zigzag"``: the forward runs entirely in zigzag sequence
    order (see :func:`forward`); targets are aligned by the same
    permutation, and the mean is order-invariant.

    Computes the head through :func:`_head_ce` — logits stay in
    ``cfg.dtype`` on the loss path (bitwise-identical to
    ``_ce(forward(...))`` at float32; at bf16 it halves the loss path's
    HBM traffic, f32 softmax math unchanged).
    """
    x = _forward_hidden(
        params, tokens, cfg, mesh=mesh, seq_axis=seq_axis,
        attn_impl=attn_impl, pp_axis=pp_axis,
        n_microbatches=n_microbatches, seq_layout=seq_layout,
    )
    if seq_layout == "zigzag":
        from ..parallel.ring_attention import _zigzag_perm

        perm, _ = _zigzag_perm(tokens.shape[1], mesh.shape[seq_axis])
        targets = targets[:, perm]
    return _head_ce(params, x, targets, cfg)


# ---------------------------------------------------------------------------
# 1F1B pipeline pieces (see parallel.pipeline.pipeline_value_and_grad):
# embedding on stage 0, blocks pipelined, loss head inside the last stage.


def pp_pieces(cfg: LlamaConfig, *, mesh=None, attn_impl: str = "auto"):
    """``(embed_fn, block_fn, head_loss_fn)`` for the 1F1B schedule."""
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
    cfg: LlamaConfig,
    *,
    mesh,
    pp_axis: str = "pp",
    n_microbatches: int = 1,
    attn_impl: str = "auto",
):
    """``(loss, grads)`` via the 1F1B pipeline — a drop-in replacement for
    ``jax.value_and_grad(loss_fn)`` when training pipeline-parallel, with
    O(P) live activations instead of O(M + P) (GPipe autodiff)."""
    from ..parallel.pipeline import pipeline_value_and_grad

    embed_fn, block_fn, head_loss_fn = pp_pieces(
        cfg, mesh=mesh, attn_impl=attn_impl
    )
    loss, (g_ep, g_lp, g_hp) = pipeline_value_and_grad(
        {"embed": params["embed"]},
        params["layers"],
        {"norm": params["norm"], "lm_head": params["lm_head"]},
        tokens,
        targets,
        embed_fn,
        block_fn,
        head_loss_fn,
        mesh=mesh,
        axis=pp_axis,
        n_microbatches=n_microbatches,
    )
    grads = {
        "embed": g_ep["embed"],
        "layers": g_lp,
        "norm": g_hp["norm"],
        "lm_head": g_hp["lm_head"],
    }
    return loss, grads
