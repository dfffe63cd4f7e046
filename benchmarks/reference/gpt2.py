"""Plain GPT-2 forward (Radford et al. 2019; the layer equations of
``transformers``' ``GPT2LMHeadModel``): learned positions, pre-LayerNorm
blocks, fused QKV with bias, GELU (tanh form, ``gelu_new``), tied output
head.  No cache, no pages, no kernel.  Reads the repo's stacked parameter
layout (``layers.*`` with a leading layer axis) and upcasts one layer at a
time inside the scan, so a 1.5B model in float32 never exists whole.
"""

import jax
import jax.numpy as jnp

from . import common


def _ln(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(
        x.dtype
    )


def hidden(params, tokens, sizes, dtype):
    """``tokens (B, T)`` -> final hidden states after ``ln_f``, ``dtype``."""
    n_head, eps = sizes["n_head"], sizes["layer_norm_epsilon"]
    b, t = tokens.shape
    x = (
        params["wte"]["weight"][tokens].astype(dtype)
        + params["wpe"]["weight"][:t].astype(dtype)[None]
    )

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        h = _ln(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps)
        qkv = h @ lp["attn_qkv"]["weight"] + lp["attn_qkv"]["bias"]
        q, k, v = (
            z.reshape(b, t, n_head, -1) for z in jnp.split(qkv, 3, axis=-1)
        )
        a = common.causal_attention(q[:, :, :, None], k, v)
        x = x + a @ lp["attn_proj"]["weight"] + lp["attn_proj"]["bias"]
        h = _ln(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps)
        h = jax.nn.gelu(
            h @ lp["mlp_fc"]["weight"] + lp["mlp_fc"]["bias"],
            approximate=True,
        )
        return x + h @ lp["mlp_proj"]["weight"] + lp["mlp_proj"]["bias"], None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _ln(x, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)


def head(params, x, dtype):
    return (x @ params["wte"]["weight"].astype(dtype).T).astype(jnp.float32)
