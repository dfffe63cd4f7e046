"""Plain Jamba forward (the layer equations of ``transformers`` 4.57's
``JambaForCausalLM`` with ``num_experts = 1``, its mixer's
``slow_forward``; ``tests/test_jamba.py`` holds it against that module):
pre-RMSNorm blocks, layer ``i`` an attention layer iff ``i %
attn_layer_period == attn_layer_offset`` and a Mamba layer otherwise, a
SwiGLU feed-forward after every mixer, a final norm, the head tied to the
embedding.

* Mamba mixer: ``(u, z) = split(h W_in)``; ``u = silu(conv(u) + b)``,
  causal and depthwise over zeros to the left; ``(d, B, C) = split(u W_x)``,
  each RMS-normed; ``delta = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``;
  the recurrence ``h_t = exp(delta_t A) h_{t-1} + delta_t u_t B_t``, ``y_t =
  h_t C_t + D u_t`` as ONE ``lax.scan`` step a position, no chunk and no
  kernel; ``(y silu(z)) W_out``.
* Attention: multi-query (``num_key_value_heads`` key/value heads), NO
  position term, causal softmax at ``head_dim**-0.5``.

Departures from the published module, each deliberate: the state and the
scan are float32 whatever ``dtype`` is (``slow_forward`` starts the state in
the model's dtype and casts it to that dtype before the product with ``C``;
the CUDA kernels the published model runs with keep it float32); the
embedding's row 0 (``pad_token_id``) is an ordinary row.

Reads the repo's period stacks (``periods``: ``mamba_a``, ``attn``,
``mamba_b``) and upcasts one layer at a time.  Differentiable as it stands
(``drivers/train_steps_ssm`` compares the step's gradient with ``jax.grad``
of this forward).  The layers, the query blocks and stretches of
``SCAN_BLOCK`` positions of the scan are ``jax.checkpoint``ed: in a forward
nothing changes, in a gradient each is computed again from its inputs with
the same operations, so the numbers are those of the plain program and
neither a layer's 8,192 x 8,192 scores nor its 8,192 x 5,120 x 16 states
are ever all kept.
"""

import jax
import jax.numpy as jnp

from . import common

SCAN_BLOCK = 256


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def causal_attention(q, k, v):
    """``q (B, T, H, Dh)``, ``k``/``v (B, T, Hkv, Dh)`` -> ``(B, T, H*Dh)``:
    ``common.causal_attention``'s products, query rows in blocks of
    ``common.Q_BLOCK``, one block after another."""
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, t, hkv, h // hkv, dh)
    size = min(common.Q_BLOCK, t)
    full = t // size * size

    @jax.checkpoint
    def block(qb_rows):
        qb, rows = qb_rows
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(dh))
        mask = jnp.arange(t)[None, :] <= rows[:, None]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
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


def conv(u, w, b):
    """``u (B, T, C)``, taps ``w (K, C)``: ``out_t = b + sum_k w_k
    u_{t-K+1+k}`` over zeros to the left."""
    taps, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return b + sum(padded[:, k:k + t] * w[k] for k in range(taps))


def scan(u, delta, a, b, c, d):
    """The recurrence, a position a step, float32: ``u``/``delta (B, T,
    C)``, ``a (C, N)``, ``b``/``c (B, T, N)``, ``d (C,)`` -> ``(B, T, C)``."""
    u, delta, b, c = (x.astype(jnp.float32) for x in (u, delta, b, c))
    bsz, t, ch = u.shape

    def step(h, x):
        u_t, dt_t, b_t, c_t = x
        h = (
            jnp.exp(dt_t[..., None] * a) * h
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        )
        return h, (h * c_t[:, None, :]).sum(-1)

    @jax.checkpoint
    def stretch(h, xs):
        return jax.lax.scan(step, h, xs)

    xs = tuple(x.swapaxes(0, 1) for x in (u, delta, b, c))  # time-major
    h = jnp.zeros((bsz, ch, a.shape[1]), jnp.float32)
    size = min(SCAN_BLOCK, t)
    full = t // size * size
    h, y = jax.lax.scan(
        stretch, h,
        tuple(x[:full].reshape(-1, size, *x.shape[1:]) for x in xs),
    )
    y = y.reshape(full, bsz, ch)
    if full < t:
        _, rest = stretch(h, tuple(x[full:] for x in xs))
        y = jnp.concatenate([y, rest])
    return y.swapaxes(0, 1) + d.astype(jnp.float32) * u


def mamba(h, lp, sizes):
    """The mixer on normed ``h (B, T, D)``."""
    eps, n, r = sizes["rms_norm_eps"], sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    uz = h @ lp["w_in"]
    u, z = jnp.split(uz, 2, axis=-1)
    u = jax.nn.silu(conv(u, lp["conv_w"], lp["conv_b"]))
    p = u @ lp["w_x"]
    dt = _rms(p[..., :r], lp["dt_norm"], eps)
    b = _rms(p[..., r:r + n], lp["b_norm"], eps)
    c = _rms(p[..., r + n:], lp["c_norm"], eps)
    delta = jax.nn.softplus(dt @ lp["w_dt"] + lp["b_dt"])
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    y = scan(u, delta, a, b, c, lp["d"]).astype(h.dtype)
    return (y * jax.nn.silu(z)) @ lp["w_out"]


def attn(h, lp, sizes):
    b, t, _ = h.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    q = (h @ lp["wq"]).reshape(b, t, heads, -1)
    k = (h @ lp["wk"]).reshape(b, t, kv, -1)
    v = (h @ lp["wv"]).reshape(b, t, kv, -1)
    return causal_attention(q, k, v) @ lp["wo"]


def _layer(mixer, sizes, dtype):
    eps = sizes["rms_norm_eps"]

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        x = x + mixer(_rms(x, lp["mixer_norm"], eps), lp, sizes)
        h = _rms(x, lp["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"], None

    return jax.checkpoint(layer)


def hidden(params, tokens, sizes, dtype):
    """``tokens (B, T)`` -> final hidden states after the last norm.
    ``params["layer0"]``, where given, holds layer 0's leaves apart from
    the stacks (whose first layer is then passed over): the driver's
    gradient is taken with respect to them."""
    x = params["embed"]["weight"][tokens].astype(dtype)
    layers = {"mamba": _layer(mamba, sizes, dtype), "attn": _layer(attn, sizes, dtype)}
    periods = params["periods"]
    first = params.get("layer0")
    for p in range(periods["attn"]["wq"].shape[0]):  # a period after another
        pp = jax.tree.map(lambda a: a[p], periods)
        for name in ("mamba_a", "attn", "mamba_b"):
            if name not in pp:
                continue
            kind = "attn" if name == "attn" else "mamba"
            stack = pp[name] if kind == "mamba" else jax.tree.map(lambda a: a[None], pp[name])
            if first is not None:
                x, _ = layers[kind](x, first)
                stack, first = jax.tree.map(lambda a: a[1:], stack), None
            x, _ = jax.lax.scan(layers[kind], x, stack)
    return _rms(x, params["norm"]["weight"].astype(dtype), sizes["rms_norm_eps"])


def head(params, x, dtype):
    """The tied head: logits over the embedding's rows."""
    return (x @ params["embed"]["weight"].astype(dtype).T).astype(jnp.float32)
