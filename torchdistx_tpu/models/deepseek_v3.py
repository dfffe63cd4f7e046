"""DeepSeek-V3 family (the layer equations of ``transformers``'
``DeepseekV3ForCausalLM``, ``q_lora_rank = None``): latent (MLA) attention
and routed experts beside shared ones — the training path.

Per layer, pre-RMSNorm on both sub-blocks, no bias anywhere:

* attention: ``q = h W_q`` per head ``[q_nope | q_rope]``; ``[c | k_rope] =
  h W_kva``, ``c = RMSNorm(c)``, ``[k_nope | v] = c W_kvb`` per head; RoPE
  on interleaved pairs for ``q_rope`` and for the ONE ``k_rope`` all heads
  share; ``k = [k_nope | k_rope]``; causal softmax attention with
  ``(nope + rope)``-wide q, k and ``v_dim``-wide v, scale
  ``(nope + rope)**-0.5`` (the flash kernels take the value width on its
  own); output projection;
* the first ``n_dense_layers`` layers: a SwiGLU feed-forward;
* the other layers: :func:`~torchdistx_tpu.ops.routed_experts
  .routed_experts` with sigmoid scores, selection on score + bias
  (``e_score_correction_bias``, a buffer the optimizer leaves alone: its
  gradient is zero), weights normalised over the ``k`` selected and scaled,
  plus a shared SwiGLU expert every token passes through.

The layer is told which experts it holds: ``n_experts`` is the router's
width, ``n_experts_held``/``first_expert_held`` the contiguous share whose
weights exist here (one chip's share under expert parallelism; all of them
by default).  What absent experts would add is left out.

Two stacks, each under one scan: ``dense_layers`` and ``moe_layers``
(experts ``(L, Eh, D, F)``).  Blocks are rematerialised with
``ops.remat.REMAT_POLICY``.  ``loss_fn`` returns ``(loss, aux)``
(``LOSS_HAS_AUX``): ``aux["moe"]`` holds the step's routing counts as
device scalars, which ``make_train_step`` hands on in its metrics.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from ..ops.attention import attention
from ..ops.remat import REMAT_POLICY
from ..ops.routed_experts import routed_experts
from . import llama as llama_mod

__all__ = [
    "DeepseekV3Config",
    "LOSS_HAS_AUX",
    "deepseek_v3_test",
    "init_params",
    "abstract_params",
    "param_specs",
    "forward",
    "loss_fn",
    "moe_block",
    "num_params",
]

# loss_fn returns (loss, aux): make_train_step differentiates with has_aux
# and merges aux into the step's metrics.
LOSS_HAS_AUX = True


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    dim: int = 7168
    n_dense_layers: int = 3
    n_moe_layers: int = 58
    n_heads: int = 128
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    ffn_dim: int = 18432  # the dense layers' feed-forward
    expert_dim: int = 2048  # one routed expert
    shared_dim: int = 2048  # the shared experts as one feed-forward
    n_experts: int = 256  # the router's width
    experts_per_token: int = 8
    routed_scale: float = 2.5
    # The share held here: all experts unless told otherwise.
    n_experts_held: Optional[int] = None
    first_expert_held: int = 0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @property
    def n_layers(self) -> int:
        return self.n_dense_layers + self.n_moe_layers


def deepseek_v3_test() -> DeepseekV3Config:
    return DeepseekV3Config(
        vocab_size=256, dim=64, n_dense_layers=1, n_moe_layers=2, n_heads=4,
        qk_nope_dim=24, qk_rope_dim=8, v_dim=16, kv_rank=16, ffn_dim=96,
        expert_dim=32, shared_dim=64, n_experts=8, experts_per_token=2,
        routed_scale=2.448, rope_theta=1e6, dtype=jnp.float32, remat=False,
    )


def _attn_shapes(cfg, n):
    D, H = cfg.dim, cfg.n_heads
    return {
        "attn_norm": (n, D),
        "wq": (n, D, H * cfg.qk_dim),
        "wkv_a": (n, D, cfg.kv_rank + cfg.qk_rope_dim),
        "kv_norm": (n, cfg.kv_rank),
        "wkv_b": (n, cfg.kv_rank, H * (cfg.qk_nope_dim + cfg.v_dim)),
        "wo": (n, H * cfg.v_dim, D),
        "mlp_norm": (n, D),
    }


def _shapes(cfg: DeepseekV3Config) -> dict:
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


def abstract_params(cfg: DeepseekV3Config):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype), _shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def param_specs(
    cfg: DeepseekV3Config, *, tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
):
    """FSDP + Megatron-TP specs matching :func:`abstract_params`: column
    projections shard their out dim over ``tp``, row projections their in
    dim, the other large dim over ``fsdp``; the latent down-projection,
    norms, router and bias replicate.  The held experts are NOT spread over
    a mesh axis: a chip of an expert-parallel deployment runs this program
    with its own ``first_expert_held``."""
    col, row = P(None, fsdp, tp), P(None, tp, fsdp)
    attn = {
        "attn_norm": P(), "wq": col, "wkv_a": P(None, fsdp, None),
        "kv_norm": P(), "wkv_b": P(None, None, tp), "wo": row,
        "mlp_norm": P(),
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


def init_params(key, cfg: DeepseekV3Config):
    """N(0, 0.02) for every matrix (``initializer_range``), ones for norms,
    zeros for the selection bias; per-leaf ``fold_in`` keys."""
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
        if isinstance(tree, tuple):
            return leaf(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(_shapes(cfg))


def num_params(cfg: DeepseekV3Config) -> int:
    import math

    return sum(
        math.prod(s) for s in jax.tree.leaves(
            _shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
        )
    )


# ---------------------------------------------------------------------------
# Forward


def _pair_swap(width: int, dtype):
    """``P (width, width)`` that turns each interleaved pair a quarter:
    ``(x @ P)[2i] = -x[2i+1]``, ``(x @ P)[2i+1] = x[2i]``.  Every output is
    one input times +-1, so the product is exact in any dtype.  Built from
    an iota, so XLA folds it and a remat saves no constant for it."""
    col = jnp.arange(width)
    partner = col[:, None] == (col ^ 1)[None, :]
    return (partner * jnp.where(col % 2 == 1, 1, -1)[None, :]).astype(dtype)


def _each_twice(t):
    """``(..., n) -> (..., 2n)``, each column twice side by side, by a
    product with zeros and ones: exact, and no ``(..., n, 2)`` array on the
    way, which ``jnp.repeat`` makes."""
    n = t.shape[-1]
    twice = jnp.arange(n)[:, None] == jnp.arange(2 * n) // 2
    return jnp.matmul(
        t, twice.astype(t.dtype), precision=jax.lax.Precision.HIGHEST
    )


def _rope_in_place(x, cos, sin, swap):
    """RoPE on interleaved pairs ``(x0, x1), (x2, x3), ...`` where they lie
    (``apply_rotary_pos_emb_interleave``'s rotation without its gather):
    ``out[2i] = x[2i] cos_i - x[2i+1] sin_i``, ``out[2i+1] = x[2i+1] cos_i +
    x[2i] sin_i``, with ``cos``, ``sin`` holding each angle twice and
    ``swap`` from :func:`_pair_swap`.  q and k come out in the same order,
    so their products are those of the interleaved form."""
    turned = jnp.matmul(x, swap, precision=jax.lax.Precision.HIGHEST)
    return x * cos + turned * sin


def _latent_up(c, wkv_b, n_heads: int, nope: int):
    """``(k_nope, v)`` per head from the normed latent ``c (B, S, rank)``,
    each from a product of its own: the weight ``(rank, H * (nope + v))`` is
    cut at each head's column ``nope`` (megabytes), never the activation
    ``(B, S, H, nope + v)``.  Every output column is the dot product the
    joint ``c @ wkv_b`` has in that place."""
    w = wkv_b.reshape(wkv_b.shape[0], n_heads, -1)
    return (
        jnp.einsum("bsr,rhd->bshd", c, w[..., :nope]),
        jnp.einsum("bsr,rhd->bshd", c, w[..., nope:]),
    )


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _attn(x, lp, cfg: DeepseekV3Config, *, mesh, attn_impl):
    # The sub-scopes are the names every family gives the same work
    # (docs/observability.md, "Scopes inside the train step"); ``concat``
    # is latent attention's own.  What stays at ``attn`` is the kernels
    # and, inside ``attention``, their ``relayout`` and ``delta``.
    # q, k and v are each written once, in the form the kernels take: what
    # has to be cut apart is cut in a weight's columns, not in an
    # activation (each slice or join of one is a pass in the forward, in
    # the remat's forward and in the backward).
    b, s, _ = x.shape
    nope, rope, H = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.n_heads
    with jax.named_scope("attn"):
        with jax.named_scope("norm"):
            h = llama_mod._rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        with jax.named_scope("proj_in"):
            q = (h @ lp["wq"]).reshape(b, s, H, cfg.qk_dim)
            kva = h @ lp["wkv_a"]
            c = llama_mod._rmsnorm(
                kva[..., : cfg.kv_rank], lp["kv_norm"], cfg.norm_eps
            )
            k_nope, v = _latent_up(c, lp["wkv_b"], H, nope)
        with jax.named_scope("rope"):
            _telemetry.counter("attn.rope", form="in_place").add()
            # a pair's two members turn by the same angle
            cos, sin = map(_each_twice, llama_mod._rope_tables(
                jnp.arange(s)[None], cfg.rope_theta, rope // 2, x.dtype
            ))
            swap = _pair_swap(rope, x.dtype)
            k_rope = _rope_in_place(
                kva[..., cfg.kv_rank:][:, :, None, :], cos, sin, swap
            )
            # q over its whole width in one expression: on the no-rope
            # columns cos is 1, sin and the swap 0, and they come out as
            # they went in.  Nothing activation-sized is cut or joined.
            lead = ((0, 0), (0, 0), (0, 0), (nope, 0))
            q = _rope_in_place(
                q, jnp.pad(cos, lead, constant_values=1), jnp.pad(sin, lead),
                jnp.pad(swap, ((nope, 0), (nope, 0))),
            )
        with jax.named_scope("concat"):
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (b, s, H, rope))], axis=-1
            )
        a = attention(q, k, v, causal=True, impl=attn_impl, mesh=mesh)
        with jax.named_scope("proj_out"):
            return x + a.reshape(b, s, H * cfg.v_dim) @ lp["wo"]


def moe_block(h, lp, cfg: DeepseekV3Config):
    """The expert sub-block on normed ``h (B, S, D)``: this share's routed
    part plus the shared expert -> ``(out, stats)``."""
    b, s, d = h.shape
    with jax.named_scope("moe"):
        routed, stats = routed_experts(
            h.reshape(b * s, d), lp["router"], lp["e_gate"], lp["e_up"],
            lp["e_down"], top_k=cfg.experts_per_token, gates="sigmoid",
            bias=lp["router_bias"], scale=cfg.routed_scale,
            first_held=cfg.first_expert_held,
        )
        with jax.named_scope("shared"):
            shared = _swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"])
        return routed.reshape(b, s, d) + shared, stats


def _build_blocks(cfg: DeepseekV3Config, *, mesh=None, attn_impl="auto"):
    """``(dense_block, moe_block)``: ``x, lp -> x`` and ``x, lp -> (x,
    (assignments to held experts, busiest held expert over their mean,
    row chunks the routed layer ran))``."""

    def dense(x, lp):
        x = _attn(x, lp, cfg, mesh=mesh, attn_impl=attn_impl)
        with jax.named_scope("mlp"):
            h = llama_mod._rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
            return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])

    def moe(x, lp):
        x = _attn(x, lp, cfg, mesh=mesh, attn_impl=attn_impl)
        with jax.named_scope("moe"):
            h = llama_mod._rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        out, stats = moe_block(h, lp, cfg)
        with jax.named_scope("moe"):
            x = x + out
        return x, (
            stats["local_assignments"], stats["load_max_over_mean"],
            stats["row_chunks"],
        )

    return dense, moe


def _forward_hidden(params, tokens, cfg, *, mesh=None, attn_impl="auto"):
    """Embedding + both stacks -> ``(x, moe)`` with ``moe`` the step's
    routing counts (device scalars)."""
    _telemetry.counter("moe.experts_held").add(cfg.held)
    _telemetry.counter("moe.experts_total").add(cfg.n_experts)
    x = llama_mod._embed(params, tokens, cfg)
    dense, moe = _build_blocks(cfg, mesh=mesh, attn_impl=attn_impl)
    if cfg.remat:
        dense = jax.checkpoint(dense, policy=REMAT_POLICY)
        moe = jax.checkpoint(moe, policy=REMAT_POLICY)
    # ``stack``: what a scan over layers does itself, a layer's weights
    # sliced out of the stack and, in its transpose, the stacked gradients
    # and residuals written; the blocks' own scopes are innermost.
    with jax.named_scope("stack"):
        x, _ = jax.lax.scan(
            lambda h, lp: (dense(h, lp), None), x, params["dense_layers"]
        )
        x, (assigned, load, chunks) = jax.lax.scan(
            moe, x, params["moe_layers"]
        )
    return x, {
        "local_assignments": assigned.sum(),
        "load_max_over_mean": load.mean(),
        "row_chunks": chunks.sum(),
    }


def forward(params, tokens, cfg: DeepseekV3Config, *, mesh=None,
            attn_impl: str = "auto"):
    """Token ids ``(B, S)`` -> logits ``(B, S, V)`` (float32)."""
    x, _ = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    return llama_mod._head_logits(params, x, cfg)


def loss_fn(params, tokens, targets, cfg: DeepseekV3Config, *, mesh=None,
            seq_axis: Optional[str] = None, attn_impl: str = "auto"):
    """``(loss, {"moe": counts})``: mean next-token cross-entropy over the
    vocabulary held, and the step's routing counts.  No balance loss (the
    published configuration has none)."""
    if seq_axis is not None:
        raise ValueError("deepseek_v3 has no sequence-parallel path")
    x, moe = _forward_hidden(
        params, tokens, cfg, mesh=mesh, attn_impl=attn_impl
    )
    return llama_mod._head_ce(params, x, targets, cfg), {"moe": moe}
