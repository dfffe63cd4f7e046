"""Plain MiniCPM-SALA forward (openbmb/MiniCPM-SALA; ``tests/test_minicpm_sala.py``
holds it against ``torchdistx_tpu/models/minicpm_sala_torch.py``, the
published architecture in torch).  ``x0 = scale_emb * embed[ids]``, then
per layer, ``c = scale_depth / sqrt(depth_scale_layers)``::

    h = RMSNorm(x)
    minicpm4:        q, k = RMSNorm_head(h W_q), RMSNorm_head(h W_k); v = h W_v
                     a = attention over the keys of the blocks chosen for
                         (token, group), j <= t; dense causal at T <= dense_len
    lightning-attn:  q, k = rope(RMSNorm_head(.)), v = h W_v
                     a = RMSNorm(sum_{s <= t} lambda_h^(t-s) (q_t . k_s) v_s / sqrt(D))
    x = x + c (a * sigmoid(h W_g)) W_o
    x = x + c W_down(silu(W_gate RMSNorm(x)) * W_up RMSNorm(x))

then ``RMSNorm(x) / (hidden_size / dim_model_base)`` (``hidden``) and the
untied head (``head``).  The choice of blocks, per (token, group): mean
keys over windows of ``sparse_kernel_size`` every ``sparse_kernel_stride``
(visible to ``t`` iff ``stride j + kernel <= t + 1``), the per-head softmax
over them summed over the group, a block's score the max over the windows
that overlap it; block 0 .. ``sparse_init_blocks - 1`` and the blocks that
overlap ``[t - window + 1, t]`` forced; then the best-scoring of the other
blocks ``<= t / block`` up to ``sparse_topk`` in all, ties to the earlier
block (a stable sort).  All float32 under ``highest`` precision; nothing
shared with the program's ``ops/``.

A layer's kind: ``mixer_types[i]`` where ``sizes`` carries the list, else
(the harness hands the reference NUMBERS only) layer ``i`` is sparse iff
``i % sparse_every_n_layers == first_sparse_layer``; ``families/
minicpm_sala.py`` refuses a configuration whose list and numbers disagree.

``hidden(..., choices=)`` takes the blocks to attend as given, one ``(B,
Hkv, T, NB)`` bool (or None: the reference's own) per sparse layer in
order: the driver takes the gradient under the PROGRAM's choice, since a
choice has no gradient and a near-tied block that the bfloat16 program
chose otherwise would move whole rows.  ``choice_scores`` gives the
reference's own choice with the scores that decided it, for the driver's
check of the program's choice.

Departures from the published description: the row gives no
``sparse_config`` (MiniCPM4-8B's is taken), no decay (Lightning's ALiBi
slopes are taken) and no weight names; the vocabulary is the share held
(the configuration says so).  So that it fits beside the step at 16,384
positions, the query rows of both mixers and the feed-forward's rows go
through in blocks, each ``jax.checkpoint``ed: in a gradient each is
computed again from its inputs with the same operations, so the numbers
are those of the plain program.
"""

import math

import jax
import jax.numpy as jnp

ROWS = 128  # query rows a block of either mixer
FF_ROWS = 2048  # rows a block of the feed-forward
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def _rope(x, theta):
    """``x (B, T, H, Dh)``: the half-split rotation over all of ``Dh``."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = (f(ang)[None, :, None, :].astype(x.dtype) for f in (jnp.cos, jnp.sin))
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def kind_of(i: int, sizes) -> str:
    if "mixer_types" in sizes:
        return sizes["mixer_types"][i]
    every, first = sizes["sparse_every_n_layers"], sizes["first_sparse_layer"]
    return SPARSE if i % every == first else LIGHTNING


def lightning_decays(sizes, layer):
    """``log(lambda_h)`` of layer ``layer``: minus the ALiBi slopes ``2^(-8
    (h + 1) / H)``, the same in every layer."""
    n = sizes["lightning_nh"]
    return -(2.0 ** (-8.0 * (jnp.arange(n, dtype=jnp.float32) + 1) / n))


# ---------------------------------------------------------------------------
# The choice of blocks.


def _windows(k, sizes):
    """Mean keys ``(B, NC, Hkv, D)`` of the windows."""
    ks, st = sizes["sparse_kernel_size"], sizes["sparse_kernel_stride"]
    t = k.shape[1]
    nc = (t - ks) // st + 1
    starts = jnp.arange(nc) * st
    idx = starts[:, None] + jnp.arange(ks)[None, :]  # (NC, ks)
    return k[:, idx].mean(2)


def group_scores(q, kc, rows, sizes):
    """``q (B, R, Hkv, G, D)`` at positions ``rows`` -> ``(B, Hkv, R, NC)``:
    the per-head softmax over the visible windows, summed over the group."""
    ks, st = sizes["sparse_kernel_size"], sizes["sparse_kernel_stride"]
    s = jnp.einsum("brhgd,bjhd->bhgrj", q, kc) / math.sqrt(q.shape[-1])
    visible = jnp.arange(kc.shape[1]) * st + ks <= rows[:, None] + 1
    s = jnp.where(visible, s, -jnp.inf)
    p = jnp.nan_to_num(jax.nn.softmax(s, axis=-1), nan=0.0)
    return p.sum(2)


def block_scores(sc, sizes, n_blocks):
    """``(..., NC)`` -> ``(..., NB)``: the max over the windows that overlap
    a block (``stride j < (b + 1) block`` and ``stride j + kernel > b
    block``)."""
    ks, st, bs = (
        sizes["sparse_kernel_size"], sizes["sparse_kernel_stride"],
        sizes["sparse_block_size"],
    )
    nc = sc.shape[-1]
    lo = -(-(jnp.arange(n_blocks) * bs - ks + 1) // st)  # first overlapping j
    span = (bs + ks) // st
    idx = lo[:, None] + jnp.arange(span)[None, :]  # (NB, span)
    ok = (idx >= 0) & (idx < nc) & (idx * st < (jnp.arange(n_blocks)[:, None] + 1) * bs)
    vals = jnp.take(sc, jnp.clip(idx, 0, nc - 1), axis=-1)  # (..., NB, span)
    return jnp.where(ok, vals, -jnp.inf).max(-1)


def forced_blocks(rows, n_blocks, sizes):
    """``(R, NB)``: block 0 .. init - 1 and the local window's blocks."""
    bs = sizes["sparse_block_size"]
    blk = jnp.arange(n_blocks)[None, :]
    t = rows[:, None]
    local = (blk + 1) * bs - 1 >= t - sizes["sparse_window_size"] + 1
    return (blk * bs <= t) & (local | (blk < sizes["sparse_init_blocks"]))


def choose(scores, rows, sizes):
    """``(chosen (..., R, NB) bool, ranked (..., R, NB) the keys ranked: the
    forced at +inf, the blocks after the row's own at -inf)``."""
    nb = scores.shape[-1]
    bs, topk = sizes["sparse_block_size"], sizes["sparse_topk"]
    causal = jnp.arange(nb)[None, :] * bs <= rows[:, None]
    forced = forced_blocks(rows, nb, sizes)
    ranked = jnp.where(
        forced, jnp.inf, jnp.where(causal, jnp.maximum(scores, -1e30), -jnp.inf)
    )
    order = jnp.argsort(-ranked, axis=-1, stable=True)[..., :topk]
    chosen = jnp.zeros(ranked.shape, bool)
    chosen = jnp.put_along_axis(chosen, order, True, axis=-1, inplace=False)
    return chosen & (ranked > -jnp.inf), ranked


def choice_scores(q, k, sizes, rows):
    """The reference's own choice for the query rows ``rows`` (a slice):
    ``q (B, T, H, D)`` and ``k (B, T, Hkv, D)`` after their norms ->
    ``(chosen, ranked)`` ``(B, Hkv, R, NB)``."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    qr = q[:, rows].reshape(b, rows.shape[0], hkv, h // hkv, d)
    sc = group_scores(qr, _windows(k, sizes), rows, sizes)
    return choose(block_scores(sc, sizes, t // sizes["sparse_block_size"]), rows, sizes)


# ---------------------------------------------------------------------------
# The mixers.


def _sparse(q, k, v, sizes, chosen=None):
    """``q (B, T, H, D)``, ``k``/``v (B, T, Hkv, D)`` -> ``(B, T, H*D)``."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    bs = sizes["sparse_block_size"]
    dense = t <= sizes["sparse_dense_len"]
    size = min(ROWS, t)
    cols = jnp.arange(t)
    kc = None if dense else _windows(k, sizes)

    @jax.checkpoint
    def block(lo):
        rows = lo + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, size, axis=1).reshape(b, size, hkv, g, d)
        seen = cols[None, :] <= rows[:, None]  # (R, T)
        if not dense:
            if chosen is None:
                sc = group_scores(jax.lax.stop_gradient(qb), jax.lax.stop_gradient(kc), rows, sizes)
                mine, _ = choose(block_scores(sc, sizes, t // bs), rows, sizes)
            else:
                mine = jax.lax.dynamic_slice_in_dim(chosen, lo, size, axis=2)
            seen = seen & jnp.repeat(mine, bs, axis=-1)  # (B, Hkv, R, T)
        else:
            seen = jnp.broadcast_to(seen, (b, hkv, size, t))
        s = jnp.einsum("brhgd,bjhd->bhgrj", qb, k) / math.sqrt(d)
        s = jnp.where(seen[:, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgrj,bjhd->brhgd", p, v).reshape(b, size, h * d)

    out = jax.lax.map(block, jnp.arange(0, t, size))
    return out.swapaxes(0, 1).reshape(b, t, h * d)


def _lightning(q, k, v, sizes, layer):
    """The quadratic form in query-row blocks: ``(B, T, H, D)`` ->
    ``(B, T, H*D)``; every decay power ``exp(a n)`` with ``n >= 0``."""
    b, t, h, d = q.shape
    a = lightning_decays(sizes, layer)
    size = min(ROWS, t)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(lo):
        rows = lo + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, size, axis=1)
        diff = (rows[:, None] - cols[None, :]).astype(jnp.float32)
        mask = jnp.where(
            diff >= 0, jnp.exp(a[:, None, None] * jnp.maximum(diff, 0.0)), 0.0
        )  # (H, R, T)
        s = jnp.einsum("brhd,bjhd->bhrj", qb, k) / math.sqrt(d)
        return jnp.einsum("bhrj,bjhd->brhd", s * mask, v).reshape(b, size, h * d)

    out = jax.lax.map(block, jnp.arange(0, t, size))
    return out.swapaxes(0, 1).reshape(b, t, h * d)


def _positions(q, k, sizes, kind):
    """Rope on the lightning layers' q and k; the sparse layers have no
    position term."""
    if kind == SPARSE:
        return q, k
    return _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])


def _o_norm(a, w, eps):
    """The lightning output's RMS norm over all heads' width."""
    return _rms(a, w, eps)


def _mixer(x, lp, sizes, kind, chosen=None, layer=0):
    b, t, _ = x.shape
    eps = sizes["rms_norm_eps"]
    if kind == SPARSE:
        n_q, n_kv, dh = (
            sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
        )
    else:
        n_q = n_kv = sizes["lightning_nh"]
        dh = sizes["lightning_head_dim"]
    h = _rms(x, lp["attn_norm"], eps)
    q = _rms((h @ lp["wq"]).reshape(b, t, n_q, dh), lp["q_norm"], eps)
    k = _rms((h @ lp["wk"]).reshape(b, t, n_kv, dh), lp["k_norm"], eps)
    v = (h @ lp["wv"]).reshape(b, t, n_kv, dh)
    q, k = _positions(q, k, sizes, kind)
    if kind == SPARSE:
        a = _sparse(q, k, v, sizes, chosen)
    else:
        a = _o_norm(_lightning(q, k, v, sizes, layer), lp["o_norm"], eps)
    return (a * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]


def _mlp(x, lp, sizes):
    """The feed-forward in row blocks (its ``(T, 16384)`` float32
    intermediates are 1 GB each at 16,384 positions)."""
    b, t, d = x.shape
    size = min(FF_ROWS, t)

    @jax.checkpoint
    def block(xb):
        h = _rms(xb, lp["mlp_norm"], sizes["rms_norm_eps"])
        return (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]

    rows = x.reshape(-1, d)
    out = jax.lax.map(block, rows.reshape(-1, size, d))
    return out.reshape(b, t, d)


def depth_scale(sizes):
    return sizes["scale_depth"] / math.sqrt(sizes["depth_scale_layers"])


def _layers(params, sizes):
    """``[(kind, layer tree)]`` in layer order from the two stacks (a kind's
    stack may come as several stacks in turn)."""
    trees = {}
    for kind, name in ((SPARSE, "sparse_layers"), (LIGHTNING, "lightning_layers")):
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


def hidden(params, tokens, sizes, dtype, choices=None):
    """``tokens (B, T)`` -> what the head reads: the final RMS norm over
    ``hidden_size / dim_model_base``.  ``choices``: per sparse layer in
    order, the blocks to attend ``(B, Hkv, T, NB)`` bool, or None."""
    c = depth_scale(sizes)
    x = params["embed"]["weight"][tokens].astype(dtype) * sizes["scale_emb"]
    choices = list(choices or [])

    def layer(x, lp, kind, i, chosen):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        x = x + c * _mixer(x, lp, sizes, kind, chosen, i)
        return x + c * _mlp(x, lp, sizes)

    for i, (kind, lp) in enumerate(_layers(params, sizes)):
        chosen = (choices.pop(0) if choices else None) if kind == SPARSE else None
        x = jax.checkpoint(layer, static_argnums=(2, 3))(x, lp, kind, i, chosen)
    x = _rms(x, params["norm"]["weight"].astype(dtype), sizes["rms_norm_eps"])
    return x / (sizes["hidden_size"] / sizes["dim_model_base"])


def head(params, x, dtype):
    return (x @ params["lm_head"]["weight"].astype(dtype)).astype(jnp.float32)


def first_sparse_qk(params, tokens, sizes, dtype):
    """The FIRST sparse layer's normed ``q``, ``k`` (the layers before it
    run as in ``hidden``), for ``choice_scores``."""
    c = depth_scale(sizes)
    x = params["embed"]["weight"][tokens].astype(dtype) * sizes["scale_emb"]
    for i, (kind, lp) in enumerate(_layers(params, sizes)):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        if kind == SPARSE:
            b, t, _ = x.shape
            eps = sizes["rms_norm_eps"]
            h = _rms(x, lp["attn_norm"], eps)
            q = (h @ lp["wq"]).reshape(b, t, sizes["num_attention_heads"], -1)
            k = (h @ lp["wk"]).reshape(b, t, sizes["num_key_value_heads"], -1)
            q, k = _rms(q, lp["q_norm"], eps), _rms(k, lp["k_norm"], eps)
            return _positions(q, k, sizes, kind)
        x = x + c * _mixer(x, lp, sizes, kind, None, i)
        x = x + c * _mlp(x, lp, sizes)
    raise ValueError("no sparse layer")
