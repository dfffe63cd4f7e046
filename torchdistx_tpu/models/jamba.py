"""Jamba family (the layer equations of ``transformers``'
``JambaForCausalLM`` with one expert, ``slow_forward`` of its Mamba mixer):
state-space (Mamba-1) layers with an attention layer every
``attn_period`` layers — the training path.

Layer ``i`` is an attention layer iff ``i % attn_period == attn_offset``,
else a Mamba layer.  Every layer, pre-RMSNorm on both sub-blocks, no bias
but the convolution's and ``dt_proj``'s::

    x = x + mixer(rmsnorm(x, mixer_norm))
    x = x + (silu(h W_gate) * (h W_up)) W_down,   h = rmsnorm(x, mlp_norm)

* Mamba mixer, ``C = expand * dim`` channels, ``N`` states, rank ``R``:
  ``(u, z) = split(h W_in)``; ``u = silu(conv(u) + b_conv)``, a causal
  depthwise convolution of ``d_conv`` taps over zeros to the left;
  ``(d, B, C) = split(u W_x, [R, N, N])``, each RMS-normed with a weight of
  its own; ``delta = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)`` in
  float32; :func:`~torchdistx_tpu.ops.selective_scan.selective_scan`
  (float32 state, the ``D * u`` skip inside); ``(y * silu(z)) W_out``.
* Attention layer: ``n_heads`` query heads on ``n_kv_heads`` key/value
  heads, NO rotary or any other position term (the state-space layers carry
  the order), causal softmax at ``head_dim**-0.5``, through
  :func:`~torchdistx_tpu.ops.attention.attention` like every family.
* After the last layer the final norm; the head is the embedding, tied,
  in blocks of rows (``_common.blocked_head_ce``).

Parameters are stacked by PERIOD (``n_layers = P * attn_period``):
``periods = {mamba_a (P, attn_offset, ...), attn (P, ...), mamba_b (P,
attn_period - attn_offset - 1, ...)}``, each layer's feed-forward and norms
beside its mixer; one scan over the periods, and inside it a scan over each
Mamba stack.  Blocks are rematerialised with ``ops.remat.REMAT_POLICY``:
beside its input a block keeps what the kernels it ran name — the
attention block the flash kernel's ``out``/``lse``, a Mamba block the
scan's ``y`` and chunk-start states (``ssm_out`` ``(B, T, d_inner)`` in
the model's dtype, ``ssm_starts`` ``(B, T/chunk, N, d_inner)`` float32;
52.4 MB a layer at 4,096 x 5,120 x 16) — so the backward recomputes the
projections, the convolution and the feed-forward, and runs each forward
kernel once a layer.

Scopes: ``mamba`` (the whole mixer) with ``in_proj``, ``conv``,
``ssm_params``, ``scan``, ``out_proj`` under it; ``attn``, ``mlp``,
``embed``, ``head``.  Counters: ``ssm.layers`` and the scan's own
(``ssm.scan{impl=}``, ``ssm.scan{interpret=}``, ``ssm.scan_chunks``),
the head's ``head.ce{grad=forward}`` and ``head.row_blocks``.

Not here yet: a cache (``init_cache`` / ``forward_cached``: recurrent state
beside pages), packed documents (the scan, the convolution and the flash
kernels know no segment), a sequence-parallel scan.  Under a mesh of
several chips the ``jnp`` scan partitions like the rest of the step and
the kernels run per shard (``shard_map``: rows over ``dp`` x ``fsdp``,
channels over ``tp``; all three above 1 is refused, see ``_scan``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from ..ops.attention import attention
from ..ops.remat import REMAT_POLICY
from ..ops.selective_scan import resolve_impl, selective_scan
from . import llama as llama_mod
from ._common import blocked_head_ce

__all__ = [
    "JambaConfig",
    "jamba_test",
    "init_params",
    "abstract_params",
    "param_specs",
    "forward",
    "loss_fn",
    "num_params",
]


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    attn_period: int = 14
    attn_offset: int = 7
    n_heads: int = 20
    n_kv_heads: int = 1
    ffn_dim: int = 8192
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    scan_impl: str = "auto"  # selective_scan's impl
    scan_chunk: int = 128

    def __post_init__(self):
        if self.n_layers % self.attn_period:
            raise ValueError(
                f"n_layers ({self.n_layers}) must be whole periods of "
                f"{self.attn_period}"
            )
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError("attn_offset must lie inside the period")

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.attn_period

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def stacks(self) -> dict:
        """Mamba layers before and after a period's attention layer."""
        return {
            "mamba_a": self.attn_offset,
            "mamba_b": self.attn_period - self.attn_offset - 1,
        }


def jamba_test() -> JambaConfig:
    """Two periods of four layers (attention second), float32."""
    return JambaConfig(
        vocab_size=256, dim=64, n_layers=8, attn_period=4, attn_offset=1,
        n_heads=4, n_kv_heads=1, ffn_dim=96, d_state=8, dt_rank=8,
        dtype=jnp.float32, remat=False, scan_chunk=16,
    )


def _mlp_shapes(cfg, lead):
    D, F = cfg.dim, cfg.ffn_dim
    return {
        "mlp_norm": lead + (D,), "w_gate": lead + (D, F),
        "w_up": lead + (D, F), "w_down": lead + (F, D),
    }


def _mamba_shapes(cfg, lead):
    D, C, N, R = cfg.dim, cfg.d_inner, cfg.d_state, cfg.dt_rank
    return {
        "mixer_norm": lead + (D,), "w_in": lead + (D, 2 * C),
        "conv_w": lead + (cfg.d_conv, C), "conv_b": lead + (C,),
        "w_x": lead + (C, R + 2 * N), "dt_norm": lead + (R,),
        "b_norm": lead + (N,), "c_norm": lead + (N,),
        "w_dt": lead + (R, C), "b_dt": lead + (C,),
        "a_log": lead + (C, N), "d": lead + (C,), "w_out": lead + (C, D),
        **_mlp_shapes(cfg, lead),
    }


def _attn_shapes(cfg, lead):
    D, hd = cfg.dim, cfg.head_dim
    return {
        "mixer_norm": lead + (D,), "wq": lead + (D, cfg.n_heads * hd),
        "wk": lead + (D, cfg.n_kv_heads * hd),
        "wv": lead + (D, cfg.n_kv_heads * hd),
        "wo": lead + (cfg.n_heads * hd, D), **_mlp_shapes(cfg, lead),
    }


def _shapes(cfg: JambaConfig) -> dict:
    p = cfg.n_periods
    periods = {"attn": _attn_shapes(cfg, (p,))}
    for name, n in cfg.stacks.items():
        if n:
            periods[name] = _mamba_shapes(cfg, (p, n))
    return {
        "embed": {"weight": (cfg.vocab_size, cfg.dim)},
        "periods": periods,
        "norm": {"weight": (cfg.dim,)},
    }


def _is_shape(x):
    return isinstance(x, tuple)


def abstract_params(cfg: JambaConfig):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype), _shapes(cfg),
        is_leaf=_is_shape,
    )


def num_params(cfg: JambaConfig) -> int:
    return sum(
        math.prod(s) for s in jax.tree.leaves(_shapes(cfg), is_leaf=_is_shape)
    )


_COLUMN = ("w_in", "w_gate", "w_up", "wq", "wk", "wv")
_ROW = ("w_out", "w_down", "wo")


def param_specs(cfg: JambaConfig, *, tp: Optional[str] = "tp",
                fsdp: Optional[str] = "fsdp"):
    """FSDP + Megatron-TP specs matching :func:`abstract_params`: column
    projections shard their out dim over ``tp``, row projections their in
    dim, the other dim over ``fsdp``; the low-rank path (``w_x``, ``w_dt``)
    shards over ``fsdp`` alone; norms, the convolution, ``A_log``, ``D`` and
    the biases replicate."""

    def spec(name, shape):
        lead = (None,) * (len(shape) - 2)
        if name in _COLUMN:
            return P(*lead, fsdp, tp)
        if name in _ROW:
            return P(*lead, tp, fsdp)
        if name in ("w_x", "w_dt"):
            return P(*lead, fsdp, None)
        return P()

    return {
        "embed": {"weight": P(fsdp, tp)},
        "periods": {
            stack: {k: spec(k, s) for k, s in leaves.items()}
            for stack, leaves in _shapes(cfg)["periods"].items()
        },
        "norm": {"weight": P()},
    }


def init_params(key, cfg: JambaConfig):
    """``transformers``' initialisation: N(0, 0.02) for every matrix and
    the convolution's taps, ones for norms and ``D``, zeros for the biases,
    ``A_log = log(1..N)``; per-leaf ``fold_in`` keys."""
    import zlib

    def leaf(path, shape):
        name = path[-1]
        if name.endswith("norm") or path[0] == "norm" or name == "d":
            return jnp.ones(shape, cfg.dtype)
        if name in ("conv_b", "b_dt"):
            return jnp.zeros(shape, cfg.dtype)
        if name == "a_log":
            row = jnp.log(jnp.arange(1, cfg.d_state + 1, dtype=jnp.float32))
            return jnp.broadcast_to(row, shape).astype(cfg.dtype)
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        return (
            jax.random.normal(leaf_key, shape, jnp.float32) * 0.02
        ).astype(cfg.dtype)

    def walk(tree, path=()):
        if _is_shape(tree):
            return leaf(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(_shapes(cfg))


# ---------------------------------------------------------------------------
# Forward


def _conv(u, w, b):
    """Causal depthwise convolution: ``u (B, T, C)``, taps ``w (K, C)``
    (tap ``k`` meets ``u_{t-K+1+k}``), zeros to the left."""
    taps, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    out = b.astype(jnp.float32)
    for k in range(taps):
        out = out + padded[:, k:k + t] * w[k].astype(jnp.float32)
    return out.astype(u.dtype)


def _over(mesh, names, size):
    """Of the mesh's axes ``names``, those that split ``size`` (or None)."""
    axes = tuple(n for n in names if mesh.shape.get(n, 1) > 1)
    if not axes or size % math.prod(mesh.shape[n] for n in axes):
        return None
    return axes


def _scan(u, delta, a, b, c, d, cfg: JambaConfig, mesh):
    """The selective scan.  The ``jnp`` scan is plain XLA and partitions
    like the rest of the step; the kernels are a custom call XLA cannot
    partition, so under a mesh of several chips each shard runs its own
    (``shard_map``): rows over ``dp`` x ``fsdp``, channels, which the
    recurrence never mixes, over ``tp``."""
    impl = resolve_impl(cfg.scan_impl)
    kw = dict(impl=impl, chunk=cfg.scan_chunk)
    if mesh is None or mesh.size == 1 or impl == "jnp":
        return selective_scan(u, delta, a, b, c, d, **kw)
    rows = _over(mesh, ("dp", "fsdp"), u.shape[0])
    chans = _over(mesh, ("tp",), u.shape[2])
    if rows is None and chans is None:
        return selective_scan(u, delta, a, b, c, d, **dict(kw, impl="jnp"))
    if chans and len(rows or ()) > 1:
        # On the CPU's virtual devices the step's loss came out wrong on
        # a dp x fsdp x tp mesh (each pair of the three was right).
        raise NotImplementedError(
            "jamba: the scan kernels under a mesh with dp, fsdp and tp all "
            f"above 1 are not verified (mesh {dict(mesh.shape)}); drop an "
            "axis or set scan_impl='jnp'"
        )
    wide, narrow = P(rows, None, chans), P(rows)
    return jax.shard_map(
        lambda *x: selective_scan(*x, **kw), mesh=mesh,
        in_specs=(wide, wide, P(chans), narrow, narrow, P(chans)),
        out_specs=wide, check_vma=False,
    )(u, delta, a, b, c, d)


def _mamba(x, lp, cfg: JambaConfig, mesh):
    """``x (B, T, D)`` plus its Mamba mixer's output."""
    C, N, R, eps = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.norm_eps
    with jax.named_scope("mamba"):
        with jax.named_scope("norm"):
            h = llama_mod._rmsnorm(x, lp["mixer_norm"], eps)
        with jax.named_scope("in_proj"):
            uz = h @ lp["w_in"]
            u, z = uz[..., :C], uz[..., C:]
        with jax.named_scope("conv"):
            u = jax.nn.silu(_conv(u, lp["conv_w"], lp["conv_b"]))
        with jax.named_scope("ssm_params"):
            p = u @ lp["w_x"]
            dt = llama_mod._rmsnorm(p[..., :R], lp["dt_norm"], eps)
            b = llama_mod._rmsnorm(p[..., R:R + N], lp["b_norm"], eps)
            c = llama_mod._rmsnorm(p[..., R + N:], lp["c_norm"], eps)
            delta = jax.nn.softplus(
                (dt @ lp["w_dt"]).astype(jnp.float32)
                + lp["b_dt"].astype(jnp.float32)
            ).astype(h.dtype)
            a = -jnp.exp(lp["a_log"].astype(jnp.float32))
        with jax.named_scope("scan"):
            y = _scan(u, delta, a, b, c, lp["d"], cfg, mesh)
        with jax.named_scope("out_proj"):
            return x + (y * jax.nn.silu(z)) @ lp["w_out"]


def _attn(x, lp, cfg: JambaConfig, *, mesh, attn_impl):
    """``x (B, T, D)`` plus its attention mixer's output."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("norm"):
            h = llama_mod._rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        with jax.named_scope("proj_in"):
            q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, hd)
            k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
            v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
        a = attention(q, k, v, causal=True, impl=attn_impl, mesh=mesh)
        with jax.named_scope("proj_out"):
            return x + a.reshape(b, s, cfg.n_heads * hd) @ lp["wo"]


def _mlp(x, lp, cfg: JambaConfig):
    with jax.named_scope("mlp"):
        h = llama_mod._rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def _build_blocks(cfg: JambaConfig, *, mesh=None, attn_impl="auto"):
    """``(mamba_block, attn_block)``, each ``x, lp -> x``: the mixer with
    its norm and residual add under the mixer's scope, then the MLP."""

    def mamba(x, lp):
        return _mlp(_mamba(x, lp, cfg, mesh), lp, cfg)

    def attn(x, lp):
        return _mlp(
            _attn(x, lp, cfg, mesh=mesh, attn_impl=attn_impl), lp, cfg
        )

    return mamba, attn


def _forward_hidden(params, tokens, cfg, *, mesh=None, attn_impl="auto"):
    """Embedding + every period -> hidden states before the final norm."""
    _telemetry.counter("ssm.layers").add(cfg.n_layers - cfg.n_periods)
    x = llama_mod._embed(params, tokens, cfg)
    mamba, attn = _build_blocks(cfg, mesh=mesh, attn_impl=attn_impl)
    if cfg.remat:
        mamba = jax.checkpoint(mamba, policy=REMAT_POLICY)
        attn = jax.checkpoint(attn, policy=REMAT_POLICY)

    # ``stack`` around both levels of scan: what the scans do themselves
    # (weights sliced out of their stacks, the stacked gradients and
    # residuals written in the transpose); the blocks' scopes are innermost.
    def stack(x, layers):
        with jax.named_scope("stack"):
            return jax.lax.scan(
                lambda h, lp: (mamba(h, lp), None), x, layers
            )[0]

    def period(x, pp):
        if "mamba_a" in pp:
            x = stack(x, pp["mamba_a"])
        x = attn(x, pp["attn"])
        if "mamba_b" in pp:
            x = stack(x, pp["mamba_b"])
        return x, None

    with jax.named_scope("stack"):
        return jax.lax.scan(period, x, params["periods"])[0]


def _tied_head(params):
    """The views ``llama._head*`` read: the final norm, and the embedding
    as the head's ``(D, V)`` matrix."""
    return {
        "norm": params["norm"],
        "lm_head": {"weight": params["embed"]["weight"].T},
    }


def _head_ce(params, x, targets, cfg: JambaConfig):
    """Final norm, then the tied head and mean cross-entropy in blocks of
    rows (:func:`~torchdistx_tpu.models._common.blocked_head_ce`, reading
    the embedding ``(V, D)`` where it lies): nothing the size of the
    ``(B * S, V)`` logits is kept for the backward pass (537 MB at 4,096 x
    65,536 in bfloat16).  At the benchmark's 4,096 tokens it is one
    block."""
    with jax.named_scope("head"):
        h = llama_mod._rmsnorm(x, params["norm"]["weight"], cfg.norm_eps)
        return blocked_head_ce(
            h.reshape(-1, h.shape[-1]),
            params["embed"]["weight"].astype(cfg.dtype),
            targets.reshape(-1), vocab_major=True,
        )


def forward(params, tokens, cfg: JambaConfig, *, mesh=None,
            attn_impl: str = "auto"):
    """Token ids ``(B, S)`` -> logits ``(B, S, V)`` (float32)."""
    x = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    with jax.named_scope("head"):
        return llama_mod._head_logits(_tied_head(params), x, cfg)


def loss_fn(params, tokens, targets, cfg: JambaConfig, *, mesh=None,
            seq_axis: Optional[str] = None, attn_impl: str = "auto"):
    """Mean next-token cross-entropy through the tied head."""
    if seq_axis is not None:
        raise ValueError("jamba has no sequence-parallel path")
    x = _forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    return _head_ce(params, x, targets, cfg)
