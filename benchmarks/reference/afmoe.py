"""Plain AFMoE forward (the layer equations of the published
``modeling_afmoe.py``, ``model_type: afmoe``; ``tests/test_afmoe.py`` holds
it against ``torchdistx_tpu/models/afmoe_torch.py``, the published
architecture in torch): ``x0 = embed[ids] * sqrt(hidden_size)``; four RMS
norms a layer, before and after each sub-block; per-head RMS norms on q and
k; rope (half-split, all ``head_dim``) on the window layers and NO position
term on the full ones; an explicit visibility mask (window layer: key ``j``
visible to query ``t`` iff ``0 <= t - j < sliding_window``; full layer: iff
``j <= t``); ``sigmoid(h W_gate)`` on the attention output before
``o_proj``; a SwiGLU feed-forward in the leading dense layers, then
sigmoid-scored experts selected on score + bias, weighted by the
normalised, scaled scores, plus the shared expert.  No kernel, no sort, no
cache: every held expert runs on every token and is weighted by a mask.

A layer's kind: ``layer_types[i]`` where ``sizes`` carries the list, else
(the harness hands the reference a configuration's NUMBERS only) layer
``i`` is full iff ``i >= first_full_layer`` and ``(i - first_full_layer) %
global_attn_every_n_layers == 0``; ``families/afmoe.py`` refuses a
configuration whose list and numbers disagree.

The share: ``num_experts`` experts are HELD here, the router is
``num_experts_total`` wide, and the held ones are ``first_expert_held ..
first_expert_held + num_experts - 1``; what the absent experts would add is
left out (departure from the published model, whose every expert is
somewhere).  ``mup_enabled`` and ``route_norm`` are taken as published
(true): the harness hands over no booleans.  Reads the repo's two stacks
(``dense_layers``, ``moe_layers`` with experts ``(L, Eh, D, F)``; the
latter one stack or several in turn) and upcasts one layer at a time, the
layers in a Python loop.

Differentiable as it stands (the driver compares the step's gradient with
``jax.grad`` of this forward).  So that it fits beside the step at 8,192
positions, the query rows go through in blocks of ``common.Q_BLOCK`` and
the held experts one after another, and each layer, query block and expert
term is ``jax.checkpoint``ed: in a forward nothing changes, in a gradient
each is computed again from its inputs with the same operations, so the
numbers are those of the plain program and a layer's 8,192 x 8,192 scores
are never all kept.
"""

import jax
import jax.numpy as jnp

from . import common

WINDOW, FULL = "sliding_attention", "full_attention"


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def _rope(x, theta):
    """``x (B, T, H, Dh)``: the half-split rotation (``rotate_half``) over
    all of ``Dh``, positions ``0 .. T-1``."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = (f(ang)[None, :, None, :].astype(x.dtype) for f in (jnp.cos, jnp.sin))
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def kind_of(i: int, sizes) -> str:
    if "layer_types" in sizes:
        return sizes["layer_types"][i]
    first, every = sizes["first_full_layer"], sizes["global_attn_every_n_layers"]
    return FULL if i >= first and (i - first) % every == 0 else WINDOW


def _visible(rows, cols, window):
    """The explicit mask: ``(len(rows), len(cols))``, true where the key at
    position ``cols[j]`` is visible to the query at position ``rows[t]``."""
    visible = cols[None, :] <= rows[:, None]
    if window is not None:
        visible &= rows[:, None] - cols[None, :] < window
    return visible


def _gated(a, g):
    """The attention output times the sigmoid of the layer's gate."""
    return a * jax.nn.sigmoid(g)


def masked_attention(q, k, v, window=None):
    """``q (B, T, H, Dh)``, ``k``/``v (B, T, Hkv, Dh)`` -> ``(B, T, H*Dh)``;
    query head ``n`` reads key/value head ``n // (H / Hkv)``.  The mask is
    explicit: key ``j`` is visible to query ``t`` iff ``j <= t`` and, with
    a window, ``t - j < window``.  Query rows in blocks of
    ``common.Q_BLOCK``, one block after another."""
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, t, hkv, h // hkv, dh)
    size = min(common.Q_BLOCK, t)
    full = t // size * size
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(qb_rows):
        qb, rows = qb_rows
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(dh))
        visible = _visible(rows, cols, window)
        s = jnp.where(visible[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
        return o.reshape(b, qb.shape[1], -1)

    out = jax.lax.map(
        block,
        (
            q[:, :full].reshape(b, -1, size, hkv, h // hkv, dh).swapaxes(0, 1),
            jnp.arange(full).reshape(-1, size),
        ),
    ).swapaxes(0, 1).reshape(b, full, -1)
    if full < t:
        out = jnp.concatenate(
            [out, block((q[:, full:], jnp.arange(full, t)))], axis=1
        )
    return out


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _attn(x, lp, sizes, kind):
    b, t, _ = x.shape
    n_q, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    h = _rms(x, lp["attn_norm"], eps)
    q = _rms((h @ lp["wq"]).reshape(b, t, n_q, dh), lp["q_norm"], eps)
    k = _rms((h @ lp["wk"]).reshape(b, t, n_kv, dh), lp["k_norm"], eps)
    v = (h @ lp["wv"]).reshape(b, t, n_kv, dh)
    window = None
    if kind == WINDOW:
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
        window = sizes["sliding_window"]
    a = _gated(masked_attention(q, k, v, window), h @ lp["wg"])
    return x + _rms(a @ lp["wo"], lp["post_attn_norm"], eps)


def routed(h, lp, sizes):
    """The held experts' part of the routed sum for ``h (..., D)``."""
    top_k = sizes["num_experts_per_tok"]
    first = sizes.get("first_expert_held", 0)
    s = jax.nn.sigmoid(
        h.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    )
    _, sel = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * sizes["route_scale"]

    @jax.checkpoint
    def term(expert):
        e, gate, up, down = expert
        w_e = (w * (sel == first + e)).sum(-1)
        return w_e[..., None] * _swiglu(h, gate, up, down).astype(jnp.float32)

    # One held expert after another, each on every token.
    out, _ = jax.lax.scan(
        lambda out, expert: (out + term(expert), None),
        jnp.zeros(h.shape, jnp.float32),
        (jnp.arange(lp["e_gate"].shape[0]), lp["e_gate"], lp["e_up"], lp["e_down"]),
    )
    return out.astype(h.dtype)


def _layers(stacks):
    """The layers of one stack, or of several in turn, one tree each."""
    out = []
    for stack in stacks if isinstance(stacks, (list, tuple)) else [stacks]:
        n = jax.tree.leaves(stack)[0].shape[0]
        out += [jax.tree.map(lambda a, i=i: a[i], stack) for i in range(n)]
    return out


def hidden(params, tokens, sizes, dtype):
    """``tokens (B, T)`` -> final hidden states after the last norm."""
    eps = sizes["rms_norm_eps"]
    x = params["embed"]["weight"][tokens].astype(dtype)
    x = x * (sizes["hidden_size"] ** 0.5)  # mup_enabled

    def dense(x, lp, kind):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        x = _attn(x, lp, sizes, kind)
        h = _rms(x, lp["mlp_norm"], eps)
        m = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x + _rms(m, lp["post_mlp_norm"], eps)

    def moe(x, lp, kind):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        x = _attn(x, lp, sizes, kind)
        h = _rms(x, lp["mlp_norm"], eps)
        m = _swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"]) + routed(h, lp, sizes)
        return x + _rms(m, lp["post_mlp_norm"], eps)

    i = 0
    for layer, stacks in ((dense, params["dense_layers"]), (moe, params["moe_layers"])):
        for lp in _layers(stacks):
            x = jax.checkpoint(layer, static_argnums=2)(x, lp, kind_of(i, sizes))
            i += 1
    return _rms(x, params["norm"]["weight"].astype(dtype), eps)


def head(params, x, dtype):
    return (x @ params["lm_head"]["weight"].astype(dtype)).astype(jnp.float32)
