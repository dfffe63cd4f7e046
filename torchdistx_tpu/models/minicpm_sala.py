"""MiniCPM-SALA family (openbmb/MiniCPM-SALA, ``model_type: minicpm_sala``):
block-sparse attention that picks its own blocks per token (``minicpm4``,
InfLLM-v2) and decayed linear attention (``lightning-attn``, Lightning
Attention-2) mixed by layer, a dense SwiGLU feed-forward after every mixer,
MiniCPM's muP scalings (arXiv:2404.06395) — the training path.

No bias anywhere; every RMS norm computes in float32.  ``x0 = scale_emb *
embed[ids]``, then per layer ``i``, with ``c = scale_depth /
sqrt(residual_layers)`` (the PUBLISHED depth, however many layers run)::

    h = rmsnorm(x, attn_norm)
    mixer_types[i] == "minicpm4":                 # sparse attention
        q = rmsnorm_head(h W_q, q_norm)           # H heads of head_dim
        k = rmsnorm_head(h W_k, k_norm);  v = h W_v      # Hkv heads
        a = sparse_attention(q, k, v)             # no position term
    "lightning-attn":
        q, k = rope(rmsnorm_head(h W_q, q_norm), rmsnorm_head(h W_k, k_norm))
        v = h W_v                                 # lightning heads
        a = rmsnorm(lightning(q, k, v), o_norm)   # over all heads' width
    x = x + c * (a * sigmoid(h W_g)) W_o
    h = rmsnorm(x, mlp_norm)
    x = x + c * (silu(h W_gate) * (h W_up)) W_down

and ``logits = (rmsnorm(x, norm) / (dim / dim_model_base)) W_head``
(untied).  The two mixers are :func:`~torchdistx_tpu.ops.sparse_attention
.sparse_attention` (plain causal attention through the flash kernels at
``T <= sparse.dense_len``) and :func:`~torchdistx_tpu.ops
.lightning_attention.lightning_attention` with ``lambda_h = exp(-2^(-8 (h
+ 1) / H))``.

A layer's kind is STATIC.  Two stacks, ``sparse_layers`` and
``lightning_layers``, each ``(L_kind, ...)`` in layer order (the kinds'
shapes differ: 2 key/value heads against 32).  The layers run as one scan
over the whole periods of ``mixer_types``, the period's layers one after
another in the body, each indexing its own kind's slice of the period
(``_run_layers``, the layout of ``afmoe._run_stack`` for two stacks), and
the layers after the last whole period unrolled.  Each layer is
rematerialised with ``ops.remat.REMAT_POLICY``: it keeps its input, the
sparse layer ``sparse_choice``, ``sparse_out``, ``sparse_lse`` (or, dense,
``flash_out``, ``flash_lse``), the lightning layer ``lightning_out`` and
``lightning_starts``.  The head and the loss go in blocks of rows
(``_common.blocked_head_ce``).  ``loss_fn`` returns ``(loss, aux)``
(``LOSS_HAS_AUX``) with ``aux["sparse"]["kv_blocks"]`` where the sparse
path ran: the mean over the sparse layers of the keys a q tile's kernel
steps run, in blocks.

Scopes: ``embed``; ``attn`` (sparse layers) with ``norm``, ``proj_in``,
``qk_norm``, ``select``, ``sparse``, ``gate``, ``proj_out``;
``lightning`` with ``norm``, ``proj_in``, ``qk_norm``, ``rope``,
``chunks``, ``o_norm``, ``gate``, ``proj_out``; ``mlp``; ``head``;
``stack``.  Counters: the ops' own (``sparse_attn.*``, ``lightning*``).

The sparse kernels run on one device (a mesh of more raises with
``attn_impl="pallas"``; ``jnp`` partitions anywhere).  Not here yet: a
cache (no serving path).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.lightning_attention import lightning_attention, lightning_decays
from ..ops.remat import REMAT_POLICY
from ..ops.sparse_attention import SparseConfig, sparse_attention
from . import afmoe as afmoe_mod
from . import llama as llama_mod
from ._common import blocked_head_ce

__all__ = [
    "MiniCPMSALAConfig",
    "LOSS_HAS_AUX",
    "SPARSE",
    "LIGHTNING",
    "minicpm_sala_test",
    "init_params",
    "abstract_params",
    "param_specs",
    "forward",
    "loss_fn",
    "num_params",
    "sparse_choice",
]

LOSS_HAS_AUX = True

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# The published mixer_types (32 layers).
PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32)
)


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32  # the sparse layer's query heads
    n_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32  # lightning_nh = lightning_nkv
    lightning_head_dim: int = 128
    ffn_dim: int = 16384
    mixer_types: Optional[Tuple[str, ...]] = None  # None: as published
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    residual_layers: int = 32  # the published depth the residual scale reads
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    sparse: SparseConfig = SparseConfig()
    lightning_chunk: int = 256
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        kinds = self.mixer_types
        kinds = PUBLISHED_MIXERS if kinds is None else tuple(kinds)
        object.__setattr__(self, "mixer_types", kinds)
        if len(kinds) != self.n_layers or set(kinds) - {SPARSE, LIGHTNING}:
            raise ValueError(
                f"mixer_types must name {self.n_layers} layers as {SPARSE!r} "
                f"or {LIGHTNING!r}, got {kinds}"
            )
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def n_sparse(self) -> int:
        return sum(k == SPARSE for k in self.mixer_types)

    @property
    def n_lightning(self) -> int:
        return self.n_layers - self.n_sparse

    @property
    def depth_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.residual_layers)


def minicpm_sala_test() -> MiniCPMSALAConfig:
    """Two periods of sparse, lightning x 3 at 128 positions above a
    ``dense_len`` of 32: 4 query heads on 2, 4 lightning heads."""
    return MiniCPMSALAConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, lightning_heads=4, lightning_head_dim=16, ffn_dim=96,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, LIGHTNING) * 2,
        residual_layers=8, dim_model_base=16,
        sparse=SparseConfig(
            kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8,
            window_size=16, topk=6, dense_len=32,
        ),
        lightning_chunk=32, dtype=jnp.float32, remat=False,
    )


def _shapes(cfg: MiniCPMSALAConfig) -> dict:
    D, V, F = cfg.dim, cfg.vocab_size, cfg.ffn_dim
    Ls, Ll = cfg.n_sparse, cfg.n_lightning
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hl, hdl = cfg.lightning_heads, cfg.lightning_head_dim

    def mlp(L):
        return {
            "mlp_norm": (L, D), "w_gate": (L, D, F), "w_up": (L, D, F),
            "w_down": (L, F, D),
        }

    return {
        "embed": {"weight": (V, D)},
        "sparse_layers": {
            "attn_norm": (Ls, D), "wq": (Ls, D, H * hd), "wk": (Ls, D, Hkv * hd),
            "wv": (Ls, D, Hkv * hd), "wg": (Ls, D, H * hd), "wo": (Ls, H * hd, D),
            "q_norm": (Ls, hd), "k_norm": (Ls, hd), **mlp(Ls),
        },
        "lightning_layers": {
            "attn_norm": (Ll, D), "wq": (Ll, D, Hl * hdl), "wk": (Ll, D, Hl * hdl),
            "wv": (Ll, D, Hl * hdl), "wg": (Ll, D, Hl * hdl),
            "wo": (Ll, Hl * hdl, D), "q_norm": (Ll, hdl), "k_norm": (Ll, hdl),
            "o_norm": (Ll, Hl * hdl), **mlp(Ll),
        },
        "norm": {"weight": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def _is_shape(x):
    return isinstance(x, tuple)


def abstract_params(cfg: MiniCPMSALAConfig):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype), _shapes(cfg),
        is_leaf=_is_shape,
    )


def num_params(cfg: MiniCPMSALAConfig) -> int:
    return sum(
        math.prod(s) for s in jax.tree.leaves(_shapes(cfg), is_leaf=_is_shape)
    )


def param_specs(
    cfg: MiniCPMSALAConfig, *, tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
):
    """FSDP + Megatron-TP specs matching :func:`abstract_params`: column
    projections shard their out dim over ``tp``, row projections their in
    dim, the other large dim over ``fsdp``; norms replicate."""
    col, row = P(None, fsdp, tp), P(None, tp, fsdp)

    def layers(extra):
        return {
            "attn_norm": P(), "mlp_norm": P(), "q_norm": P(), "k_norm": P(),
            "wq": col, "wk": col, "wv": col, "wg": col, "wo": row,
            "w_gate": col, "w_up": col, "w_down": row, **extra,
        }

    return {
        "embed": {"weight": P(fsdp, tp)},
        "sparse_layers": layers({}),
        "lightning_layers": layers({"o_norm": P()}),
        "norm": {"weight": P()},
        "lm_head": {"weight": P(fsdp, tp)},
    }


def init_params(key, cfg: MiniCPMSALAConfig):
    """N(0, 0.02) for every matrix, N(0, 1) for the embedding's rows
    (``nn.Embedding``'s own, as ``minicpm_sala_torch`` draws them), ones
    for norms; per-leaf ``fold_in`` keys."""
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


def _kernel_impl(attn_impl: str) -> str:
    return attn_impl if attn_impl in ("jnp", "pallas") else "auto"


def _qk(h, lp, n_q, n_kv, hd, cfg):
    b, s, _ = h.shape
    with jax.named_scope("proj_in"):
        q = (h @ lp["wq"]).reshape(b, s, n_q, hd)
        k = (h @ lp["wk"]).reshape(b, s, n_kv, hd)
        v = (h @ lp["wv"]).reshape(b, s, n_kv, hd)
    with jax.named_scope("qk_norm"):  # per head, over head_dim
        q = llama_mod._rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        k = llama_mod._rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    return q, k, v


def _out(a, h, lp):
    with jax.named_scope("gate"):
        a = a * jax.nn.sigmoid(h @ lp["wg"])
    with jax.named_scope("proj_out"):
        return a @ lp["wo"]


def _sparse_mixer(x, lp, cfg, *, mesh, attn_impl):
    b, s, _ = x.shape
    with jax.named_scope("attn"):
        with jax.named_scope("norm"):
            h = llama_mod._rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qk(h, lp, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg)
        a, stats = sparse_attention(
            q, k, v, cfg.sparse, impl=_kernel_impl(attn_impl),
            dense_impl=attn_impl, mesh=mesh,
        )
        return _out(a.reshape(b, s, -1), h, lp), stats


def _lightning_mixer(x, lp, cfg, *, attn_impl):
    b, s, _ = x.shape
    hl, hdl = cfg.lightning_heads, cfg.lightning_head_dim
    with jax.named_scope("lightning"):
        with jax.named_scope("norm"):
            h = llama_mod._rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qk(h, lp, hl, hl, hdl, cfg)
        with jax.named_scope("rope"):
            cos, sin = llama_mod._rope_tables(
                jnp.arange(s)[None], cfg.rope_theta, hdl // 2, x.dtype
            )
            q = llama_mod._rope_apply(q, cos, sin)
            k = llama_mod._rope_apply(k, cos, sin)
        with jax.named_scope("chunks"):
            a = lightning_attention(
                q, k, v, lightning_decays(hl), impl=_kernel_impl(attn_impl),
                chunk=cfg.lightning_chunk,
            )
        with jax.named_scope("o_norm"):
            a = llama_mod._rmsnorm(
                a.reshape(b, s, hl * hdl), lp["o_norm"], cfg.norm_eps
            )
        return _out(a, h, lp)


def _mlp(x, lp, cfg):
    with jax.named_scope("mlp"):
        h = llama_mod._rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        return (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def _build_block(cfg: MiniCPMSALAConfig, *, mesh=None, attn_impl="auto"):
    """``block(kind)``: a layer of that kind as ``x, lp -> (x, kv_blocks or
    None)``."""
    c = cfg.depth_scale

    def block_of(kind):
        def block(x, lp):
            stats = None
            if kind == SPARSE:
                y, st = _sparse_mixer(x, lp, cfg, mesh=mesh, attn_impl=attn_impl)
                stats = st.get("kv_blocks")
            else:
                y = _lightning_mixer(x, lp, cfg, attn_impl=attn_impl)
            x = x + c * y
            return x + c * _mlp(x, lp, cfg), stats

        if cfg.remat:
            return jax.checkpoint(block, policy=REMAT_POLICY)
        return block

    return block_of


def _run_layers(x, params, kinds, block_of):
    """``x`` through the layers of both stacks in the order ``kinds`` gives
    -> ``(x, [kv_blocks of each sparse layer that has them])``.

    One scan over the whole periods of ``kinds``: each stack reshaped to
    ``(periods, its layers a period, ...)``, the body holding a period's
    layers one after another, each taking the next slice of its own kind;
    the layers past the last whole period after it, unrolled."""
    n = len(kinds)
    p = afmoe_mod._period(kinds)
    whole = n // p
    names = {SPARSE: "sparse_layers", LIGHTNING: "lightning_layers"}
    blocks = [block_of(kind) for kind in kinds[:p]]

    def layers(x, stacks, order):
        seen, stats = {SPARSE: 0, LIGHTNING: 0}, []
        for block, kind in zip(blocks, order):
            lp = jax.tree.map(lambda a, j=seen[kind]: a[j], stacks[kind])
            seen[kind] += 1
            x, st = block(x, lp)
            if st is not None:
                stats.append(st)
        return x, stats

    per = {kind: sum(k == kind for k in kinds[:p]) for kind in names}
    periods = {
        kind: jax.tree.map(
            lambda a, m=per[kind]: a[: whole * m].reshape(whole, m, *a.shape[1:]),
            params[names[kind]],
        )
        for kind in names
    }
    x, stats = jax.lax.scan(
        lambda x, st: layers(x, st, kinds[:p]), x, periods
    )
    stats = [s.mean() for s in stats]
    if n > whole * p:
        rest = {
            kind: jax.tree.map(lambda a, m=per[kind]: a[whole * m:], params[names[kind]])
            for kind in names
        }
        x, tail = layers(x, rest, kinds[whole * p:])
        stats += tail
    return x, stats


def _forward_hidden(params, tokens, cfg, *, mesh=None, attn_impl="auto"):
    """Embedding + the layers -> ``(x, aux)``."""
    x = llama_mod._embed(params, tokens, cfg)
    with jax.named_scope("embed"):
        x = x * cfg.scale_emb
    block_of = _build_block(cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("stack"):
        x, stats = _run_layers(x, params, cfg.mixer_types, block_of)
    aux = {}
    if stats:
        aux["sparse"] = {"kv_blocks": sum(stats) / len(stats)}
    return x, aux


def _final(params, x, cfg):
    """The last norm over ``dim / dim_model_base``: what the head reads."""
    h = llama_mod._rmsnorm(x, params["norm"]["weight"], cfg.norm_eps)
    return h / (cfg.dim / cfg.dim_model_base)


def forward(params, tokens, cfg: MiniCPMSALAConfig, *, mesh=None,
            attn_impl: str = "auto"):
    """Token ids ``(B, S)`` -> logits ``(B, S, V)`` (float32)."""
    x, _ = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("head"):
        h = _final(params, x, cfg)
        return (h @ params["lm_head"]["weight"].astype(cfg.dtype)).astype(
            jnp.float32
        )


def loss_fn(params, tokens, targets, cfg: MiniCPMSALAConfig, *, mesh=None,
            seq_axis: Optional[str] = None, attn_impl: str = "auto"):
    """``(loss, aux)``: mean next-token cross-entropy, and
    ``aux["sparse"]["kv_blocks"]`` where the sparse path ran."""
    if seq_axis is not None:
        raise ValueError("minicpm_sala has no sequence-parallel path")
    x, aux = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("head"):
        h = _final(params, x, cfg)
        loss = blocked_head_ce(
            h.reshape(-1, h.shape[-1]),
            params["lm_head"]["weight"].astype(cfg.dtype),
            targets.reshape(-1), vocab_major=False,
        )
    return loss, aux


def sparse_choice(params, tokens, cfg: MiniCPMSALAConfig):
    """The blocks the FIRST sparse layer's attention chooses for
    ``tokens``, as the step computes them (the embedding, the layers before
    it, the norm, the projections and q/k norms in ``cfg.dtype``): ``(Bsz,
    Hkv, T, W)`` int32 bits (``ops.sparse_attention.select_blocks``)."""
    from ..ops.sparse_attention import select_blocks

    kinds = cfg.mixer_types
    layer = kinds.index(SPARSE)
    x = llama_mod._embed(params, tokens, cfg) * cfg.scale_emb
    if layer:
        head = dataclasses.replace(
            cfg, n_layers=layer, mixer_types=kinds[:layer], remat=False
        )
        x, _ = _run_layers(x, params, kinds[:layer], _build_block(head))
    lp = jax.tree.map(lambda a: a[0], params["sparse_layers"])
    h = llama_mod._rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, _ = _qk(h, lp, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg)
    return select_blocks(q, k, cfg.sparse)
