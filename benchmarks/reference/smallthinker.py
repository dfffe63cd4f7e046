"""Plain SmallThinker forward (PowerInfer/SmallThinker-21BA3B-Instruct,
arXiv:2507.20984; ``tests/test_smallthinker.py`` holds it against
``torchdistx_tpu/models/smallthinker_torch.py``, the published architecture
in torch).  One layer, ``x`` its input::

    l   = x W_r                       # float32 router logits from the layer's
                                      #   INPUT: before the attention's norm,
                                      #   before attention
    a   = Attn_kind(RMSNorm_1(x))     # 28-on-4 heads of 128, no bias, no q/k
                                      #   norm, no gate, scale head_dim**-0.5
    y   = x + a W_o
    S   = top_k(l);  w = softmax(l[S])     # softmax over the SELECTED logits
    u   = RMSNorm_2(y)
    out = y + sum_{e in S} w_e (relu(u G_e) * (u U_e)) D_e        # ReGLU

``kind`` full: causal, NO position term; window: rope (half-split, all
``head_dim``) on q and k, key ``j`` visible to query ``t`` iff ``0 <= t - j
< sliding_window_size``.  An explicit visibility mask; no kernel, no sort,
no cache: every held expert runs on every token and is weighted by a mask.
Then the final RMS norm and the untied head.

A layer's kind: ``sliding_window_layout[i]`` / ``rope_layout[i]`` (1: the
layer slides / ropes) where ``sizes`` carries the lists, else (the harness
hands the reference a configuration's NUMBERS only) layer ``i`` is full,
with no rope, iff ``i % full_attn_every_n_layers == first_full_layer``;
``families/smallthinker.py`` refuses a configuration whose lists and
numbers disagree.

Departures from the published description.  The share:
``moe_num_primary_experts`` experts are HELD here, the router is
``moe_num_primary_experts_total`` wide, and the held ones are
``first_expert_held .. first_expert_held + moe_num_primary_experts - 1``;
what the absent experts would add is left out (in the published model every
expert is somewhere).  ``moe_primary_router_apply_softmax`` and
``norm_topk_prob`` are taken as published (true): the harness hands over no
booleans.  The family's summary also speaks of "secondary" experts: the
published config has primary experts only, and nothing secondary is built.
Reads the repo's stack (``moe_layers`` with experts ``(L, Eh, D, F)``, one
stack or several in turn; ``dense_layers`` is empty) and upcasts one layer
at a time, the layers in a Python loop.

Differentiable as it stands (the driver compares the step's gradient with
``jax.grad`` of this forward).  So that it fits beside the step at 16,384
positions, the query rows go through in blocks of ``common.Q_BLOCK`` and
the held experts one after another, and each layer, query block and expert
term is ``jax.checkpoint``ed: in a forward nothing changes, in a gradient
each is computed again from its inputs with the same operations, so the
numbers are those of the plain program and a layer's 16,384 x 16,384
scores are never all kept.
"""

import jax
import jax.numpy as jnp

from . import common


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


def kind_of(i: int, sizes) -> tuple:
    """``(slides, ropes)`` of layer ``i``."""
    if "sliding_window_layout" in sizes:
        return bool(sizes["sliding_window_layout"][i]), bool(sizes["rope_layout"][i])
    full = i % sizes["full_attn_every_n_layers"] == sizes["first_full_layer"]
    return not full, not full


def _visible(rows, cols, window):
    """The explicit mask: ``(len(rows), len(cols))``, true where the key at
    position ``cols[j]`` is visible to the query at position ``rows[t]``."""
    visible = cols[None, :] <= rows[:, None]
    if window is not None:
        visible &= rows[:, None] - cols[None, :] < window
    return visible


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


def _attn(x, lp, sizes, slides, ropes):
    b, t, _ = x.shape
    n_q, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["head_dim"]
    h = _rms(x, lp["attn_norm"], sizes["rms_norm_eps"])
    q = (h @ lp["wq"]).reshape(b, t, n_q, dh)
    k = (h @ lp["wk"]).reshape(b, t, n_kv, dh)
    v = (h @ lp["wv"]).reshape(b, t, n_kv, dh)
    if ropes:
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    window = sizes["sliding_window_size"] if slides else None
    return x + masked_attention(q, k, v, window) @ lp["wo"]


def _reglu(u, gate, up, down):
    return (jax.nn.relu(u @ gate) * (u @ up)) @ down


def routed(x, u, lp, sizes):
    """The held experts' part of the routed sum for ``u (..., D)``, routed
    by the logits of ``x (..., D)``, the layer's input."""
    top_k = sizes["moe_num_active_primary_experts"]
    first = sizes.get("first_expert_held", 0)
    logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    top, sel = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(top, axis=-1)  # over the SELECTED logits

    @jax.checkpoint
    def term(expert):
        e, gate, up, down = expert
        w_e = (w * (sel == first + e)).sum(-1)
        return w_e[..., None] * _reglu(u, gate, up, down).astype(jnp.float32)

    # One held expert after another, each on every token.
    out, _ = jax.lax.scan(
        lambda out, expert: (out + term(expert), None),
        jnp.zeros(u.shape, jnp.float32),
        (jnp.arange(lp["e_gate"].shape[0]), lp["e_gate"], lp["e_up"], lp["e_down"]),
    )
    return out.astype(u.dtype)


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

    def layer(x, lp, slides, ropes):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        y = _attn(x, lp, sizes, slides, ropes)
        u = _rms(y, lp["mlp_norm"], eps)
        return y + routed(x, u, lp, sizes)

    for i, lp in enumerate(_layers(params["moe_layers"])):
        x = jax.checkpoint(layer, static_argnums=(2, 3))(x, lp, *kind_of(i, sizes))
    return _rms(x, params["norm"]["weight"].astype(dtype), eps)


def head(params, x, dtype):
    return (x @ params["lm_head"]["weight"].astype(dtype)).astype(jnp.float32)
