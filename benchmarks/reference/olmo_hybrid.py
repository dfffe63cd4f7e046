"""Plain Olmo-Hybrid forward (allenai/Olmo-Hybrid-7B;
``tests/test_olmo_hybrid.py`` holds it against
``torchdistx_tpu/models/olmo_hybrid_torch.py``, the published architecture
in torch).  ``x0 = embed[ids]``, then per layer, OLMo 3's post-norm block
(no norm before a sub-block)::

    h = x + RMSNorm(mixer(x))
    x = h + RMSNorm(W_down(silu(W_gate h) * W_up h))

    full_attention:    q = RMSNorm(x W_q), k = RMSNorm(x W_k) over the WHOLE
                       projection, v = x W_v; causal softmax per head at
                       1/sqrt(head_dim), no position term; W_o
    linear_attention:  q, k, v = silu(conv(x W_{q,k,v})), a causal
                       depthwise convolution of 4 taps each, no bias;
                       q = q / |q| / sqrt(d_k), k = k / |k| per head;
                       beta = 2 sigmoid(x W_b); g = -exp(A_log)
                       softplus(x W_a + dt_bias); then ONE POSITION AT A TIME
                       S_t = e^{g_t} (I - beta_t k_t k_t^T) S_{t-1}
                             + beta_t k_t v_t^T,   o_t = S_t^T q_t
                       (S float32); (RMSNorm_{d_v}(o) * silu(x W_z)) W_o

then the final RMS norm (``hidden``) and the untied head (``head``).  All
float32 under ``highest`` precision; nothing shared with the program's
``ops/``: the delta rule is the per-token recurrence, not the program's
chunk form.

A layer's kind: layer ``i`` is full iff ``i % full_attn_period ==
full_attn_offset`` (the harness hands the reference NUMBERS only;
``families/olmo_hybrid.py`` refuses a configuration whose ``layer_types``
and numbers disagree).  ``linear_allow_neg_eigval`` is true in the row
(a flag, not a number): the factor 2 on beta is written here.

Departures from the published description: the row gives no weight names
and no initialisation (FLA's and ``Qwen3Next``'s are taken), the
vocabulary is the share held (the configuration says so).  So that its
gradient fits beside the step at 16,384 positions, the recurrence runs in
blocks of ``BLOCK`` positions, the attention's query rows and the
feed-forward's rows in blocks too, each ``jax.checkpoint``ed: in a
gradient each is computed again from its inputs with the same operations,
so the numbers are those of the plain program.
"""

import math

import jax
import jax.numpy as jnp

BLOCK = 64  # positions a checkpointed block of the recurrence
ROWS = 128  # query rows a block of attention
FF_ROWS = 2048  # rows a block of the feed-forward
BETA_SCALE = 2.0  # linear_allow_neg_eigval: true
FULL, LINEAR = "full_attention", "linear_attention"


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def kind_of(i: int, sizes) -> str:
    return FULL if i % sizes["full_attn_period"] == sizes["full_attn_offset"] else LINEAR


def _conv(u, w):
    """``u (B, T, C)``, taps ``w (K, C)``: ``out_t = sum_k w_k u_{t-K+1+k}``,
    zeros before the first position."""
    taps, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[j] for j in range(taps))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _qk(q, k, d_k):
    """Per head: ``q`` to unit length over ``sqrt(d_k)``, ``k`` to unit
    length."""
    return _l2norm(q) / math.sqrt(d_k), _l2norm(k)


def _gates(x, lp):
    """``(beta, g)`` ``(B, T, H)`` float32."""
    beta = BETA_SCALE * jax.nn.sigmoid((x @ lp["wb"]).astype(jnp.float32))
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        (x @ lp["wa"]).astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)
    )
    return beta, g


def _delta(q, k, v, g, beta, sizes):
    """The recurrence one position at a time: ``q``, ``k`` ``(B, T, H,
    d_k)``, ``v (B, T, H, d_v)``, ``g``, ``beta`` ``(B, T, H)`` -> ``(B, T,
    H, d_v)``; the state float32, blocks of ``BLOCK`` positions."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    size = min(BLOCK, t)
    f32 = jnp.float32
    pad = -t % size  # a zero key with zero beta and g leaves the state alone
    q, k, v, g, beta = (
        jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        for x in (q, k, v, g, beta)
    )

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        kv = jnp.einsum("bhk,bhkv->bhv", k_t.astype(f32), s)
        s = s + jnp.einsum(
            "bhk,bhv->bhkv", k_t.astype(f32), (v_t.astype(f32) - kv) * b_t[..., None]
        )
        return s, jnp.einsum("bhk,bhkv->bhv", q_t.astype(f32), s).astype(v.dtype)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    def blocked(x):  # (B, T, ...) -> (T / size, size, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(-1, size, *x.shape[1:])

    xs = tuple(blocked(x) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), f32), xs)
    return jnp.moveaxis(o.reshape(t + pad, b, h, dv)[:t], 0, 1)


def _out_gate(o, z):
    """The gated norm's gate: ``o * silu(z)``, float32."""
    return (o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


def _gdn(x, lp, sizes):
    """The mixer in three ``jax.checkpoint``ed parts (the recurrence's
    inputs, the recurrence, its output), so that a gradient holds the
    float32 intermediates of one part at a time."""
    b, t, _ = x.shape
    h = sizes["linear_num_key_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]

    @jax.checkpoint
    def inputs(x, lp):
        q, k, v = (
            jax.nn.silu(_conv(x @ lp[w], lp[c]))
            for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v"))
        )
        q, k = _qk(q.reshape(b, t, h, dk), k.reshape(b, t, h, dk), dk)
        return (q, k, v.reshape(b, t, h, dv)) + _gates(x, lp)

    @jax.checkpoint
    def output(o, x, lp):
        o = _rms(o, lp["o_norm"], sizes["rms_norm_eps"])
        o = _out_gate(o, (x @ lp["wz"]).reshape(b, t, h, dv))
        return o.reshape(b, t, h * dv) @ lp["wo"]

    q, k, v, beta, g = inputs(x, lp)
    return output(_delta(q, k, v, g, beta, sizes), x, lp)


def _qk_norm(q, k, lp, sizes):
    """One RMS norm over the whole projection each (OLMo 3's)."""
    eps = sizes["rms_norm_eps"]
    return _rms(q, lp["q_norm"], eps), _rms(k, lp["k_norm"], eps)


def _attn(x, lp, sizes):
    b, t, d = x.shape
    h = sizes["num_attention_heads"]
    hd = d // h
    q, k = _qk_norm(x @ lp["wq"], x @ lp["wk"], lp, sizes)
    q, k, v = (a.reshape(b, t, h, hd) for a in (q, k, x @ lp["wv"]))
    return _causal(q, k, v).reshape(b, t, d) @ lp["wo"]


def _causal(q, k, v):
    """Causal softmax attention per head, query rows in blocks: ``(B, T,
    H, D)`` -> ``(B, T, H, D)``."""
    b, t, h, hd = q.shape
    size = min(ROWS, t)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(lo):
        rows = lo + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, size, axis=1)
        s = jnp.einsum("brhd,bjhd->bhrj", qb, k) / math.sqrt(hd)
        s = jnp.where(cols[None, :] <= rows[:, None], s.astype(jnp.float32), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhrj,bjhd->brhd", p, v)

    out = jax.lax.map(block, jnp.arange(0, t, size))
    return out.swapaxes(0, 1).reshape(b, t, h, hd)


def _mlp(x, lp, sizes):
    """The feed-forward and its norm in row blocks."""
    b, t, d = x.shape
    size = min(FF_ROWS, t)

    @jax.checkpoint
    def block(xb):
        y = (jax.nn.silu(xb @ lp["w_gate"]) * (xb @ lp["w_up"])) @ lp["w_down"]
        return xb + _rms(y, lp["post_ff_norm"], sizes["rms_norm_eps"])

    out = jax.lax.map(block, x.reshape(-1, size, d))
    return out.reshape(b, t, d)


def _layers(params, sizes):
    """``[(kind, layer tree)]`` in layer order from the two stacks (a kind's
    stack may come as several stacks in turn)."""
    trees = {}
    for kind, name in ((LINEAR, "linear_layers"), (FULL, "full_layers")):
        stacks = params[name]
        trees[kind] = [
            jax.tree.map(lambda a, i=i: a[i], stack)
            for stack in (stacks if isinstance(stacks, (list, tuple)) else [stacks])
            for i in range(jax.tree.leaves(stack)[0].shape[0])
        ]
    return [
        (kind, trees[kind].pop(0))
        for kind in (kind_of(i, sizes) for i in range(sizes["num_hidden_layers"]))
    ]


def hidden(params, tokens, sizes, dtype):
    """``tokens (B, T)`` -> what the head reads: the final RMS norm."""
    x = params["embed"]["weight"][tokens].astype(dtype)
    eps = sizes["rms_norm_eps"]

    def layer(x, lp, kind):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        mixer = _attn if kind == FULL else _gdn
        x = x + _rms(mixer(x, lp, sizes), lp["post_attn_norm"], eps)
        return _mlp(x, lp, sizes)

    for kind, lp in _layers(params, sizes):
        x = jax.checkpoint(layer, static_argnums=(2,))(x, lp, kind)
    return _rms(x, params["norm"]["weight"].astype(dtype), eps)


def head(params, x, dtype):
    return (x @ params["lm_head"]["weight"].astype(dtype)).astype(jnp.float32)
