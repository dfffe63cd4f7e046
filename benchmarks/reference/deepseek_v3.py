"""Plain DeepSeek-V3 forward (the layer equations of ``transformers``
4.57's ``DeepseekV3ForCausalLM`` with ``q_lora_rank = None``, against which
``tests/test_deepseek_v3.py`` holds it): pre-RMSNorm blocks, latent
attention (``[c | k_rope] = h W_kva``, ``c`` normed, ``[k_nope | v] = c
W_kvb``; RoPE on interleaved pairs for ``q_rope`` and for the one ``k_rope``
all heads share; ``(nope + rope)``-wide q and k, ``v_head_dim``-wide v,
scale ``(nope + rope)**-0.5``), a SwiGLU feed-forward in the leading dense
layers, then sigmoid-scored experts selected on score + bias
(``noaux_tc``, one group), weighted by the normalised, scaled scores, plus
the shared experts.  No kernel, no sort, no cache: every held expert runs
on every token and is weighted by a mask.

The share: ``n_routed_experts`` experts are HELD here, the router is
``n_routed_experts_total`` wide, and the held ones are
``first_expert_held .. first_expert_held + n_routed_experts - 1``; what the
absent experts would add is left out (departure from the published model,
whose every expert is somewhere).  Reads the repo's two stacks
(``dense_layers``, ``moe_layers`` with experts ``(L, Eh, D, F)``) and
upcasts one layer at a time inside the scan.

Differentiable as it stands (``drivers/train_steps_routed`` compares the
step's gradient with ``jax.grad`` of this forward).  The layers, the query
blocks and each expert's term are ``jax.checkpoint``ed: in a forward nothing
changes, in a gradient each is computed again from its inputs with the
same operations, so the numbers are those of the plain program and a
layer's 8,192 x 8,192 scores are never all kept.
"""

import jax
import jax.numpy as jnp

from . import common


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def _rope(x, theta):
    """``x (B, T, H, R)``, interleaved pairs; comes out half-split (q and k
    alike, so their products are unchanged)."""
    t, r = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = (f(ang)[None, :, None, :].astype(x.dtype) for f in (jnp.cos, jnp.sin))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v):
    """``q``/``k (B, T, H, Dqk)``, ``v (B, T, H, Dv)`` -> ``(B, T, H*Dv)``:
    ``common.causal_attention`` with a value width of its own, query rows
    in blocks of ``common.Q_BLOCK``, one block after another."""
    b, t, h, dqk = q.shape
    size = min(common.Q_BLOCK, t)
    full = t // size * size

    @jax.checkpoint
    def block(qb_rows):
        qb, rows = qb_rows
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(dqk))
        mask = jnp.arange(t)[None, :] <= rows[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return o.reshape(b, qb.shape[1], -1)

    out = jax.lax.map(
        block,
        (
            q[:, :full].reshape(b, -1, size, h, dqk).swapaxes(0, 1),
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


def _attn(x, lp, sizes):
    b, t, _ = x.shape
    n_head, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank = sizes["kv_lora_rank"]
    h = _rms(x, lp["attn_norm"], eps)
    q = (h @ lp["wq"]).reshape(b, t, n_head, nope + rope)
    kva = h @ lp["wkv_a"]
    c = _rms(kva[..., :rank], lp["kv_norm"], eps)
    kv = (c @ lp["wkv_b"]).reshape(b, t, n_head, -1)
    k_rope = _rope(kva[..., rank:][:, :, None, :], sizes["rope_theta"])
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], sizes["rope_theta"])], axis=-1
    )
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, n_head, rope))], axis=-1
    )
    return x + causal_attention(q, k, kv[..., nope:]) @ lp["wo"]


def routed(h, lp, sizes):
    """The held experts' part of the routed sum for ``h (..., D)``."""
    top_k = sizes["num_experts_per_tok"]
    first = sizes.get("first_expert_held", 0)
    s = jax.nn.sigmoid(
        h.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    )
    _, sel = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"]

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


def hidden(params, tokens, sizes, dtype):
    """``tokens (B, T)`` -> final hidden states after the last norm."""
    eps = sizes["rms_norm_eps"]
    x = params["embed"]["weight"][tokens].astype(dtype)

    def dense(x, lp):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        x = _attn(x, lp, sizes)
        h = _rms(x, lp["mlp_norm"], eps)
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None

    def moe(x, lp):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        x = _attn(x, lp, sizes)
        h = _rms(x, lp["mlp_norm"], eps)
        shared = _swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"])
        return x + routed(h, lp, sizes) + shared, None

    x, _ = jax.lax.scan(jax.checkpoint(dense), x, params["dense_layers"])
    stacks = params["moe_layers"]  # one stack, or several scanned in turn
    for stack in stacks if isinstance(stacks, (list, tuple)) else [stacks]:
        x, _ = jax.lax.scan(jax.checkpoint(moe), x, stack)
    return _rms(x, params["norm"]["weight"].astype(dtype), eps)


def head(params, x, dtype):
    return (x @ params["lm_head"]["weight"].astype(dtype)).astype(jnp.float32)
