"""Plain Llama/Mistral forward (Touvron et al. 2023; Jiang et al. 2023; the
layer equations of ``transformers``' ``MistralForCausalLM`` without a sliding
window): RMSNorm, rotary positions on half-split pairs (``rotate_half``),
grouped-query attention, SwiGLU, untied output head.  No cache, no pages, no
kernel.  Reads the repo's stacked parameter layout (linears stored
``(in, out)``) and upcasts one layer at a time inside the scan.
"""

import jax
import jax.numpy as jnp

from . import common


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """``x (B, T, H, Dh)`` rotated at positions ``0..T-1``; angles and the
    rotation itself in float32."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : dh // 2], xf[..., dh // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def hidden(params, tokens, sizes, dtype):
    """``tokens (B, T)`` -> final hidden states after the last norm."""
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    b, t = tokens.shape
    x = params["embed"]["weight"][tokens].astype(dtype)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        h = _rms(x, lp["attn_norm"], eps)
        q = _rope((h @ lp["wq"]).reshape(b, t, hq, -1), theta)
        k = _rope((h @ lp["wk"]).reshape(b, t, hkv, -1), theta)
        v = (h @ lp["wv"]).reshape(b, t, hkv, -1)
        a = common.causal_attention(
            q.reshape(b, t, hkv, hq // hkv, -1), k, v
        )
        x = x + a @ lp["wo"]
        h = _rms(x, lp["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp[
            "w_down"
        ]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["norm"]["weight"], eps)


def head(params, x, dtype):
    return (x @ params["lm_head"]["weight"].astype(dtype)).astype(jnp.float32)
