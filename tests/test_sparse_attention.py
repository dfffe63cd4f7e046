"""Block-sparse attention that picks its own blocks: the choice against a
brute-force rule written token by token (the init block, the local
window's blocks, the top-k of the rest, the causal partial block, one
choice for the whole group), the attention over the chosen keys against a
dense masked attention, the interpreted kernels against the ``jnp``
oracle forward and backward, the ``dense_len`` switch, and the counters.

CPU, float32, seeded: values and counts only.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.ops import sparse_attention as sa

# 128 positions in 16 blocks of 8; windows of 4 every 2; 6 blocks a token,
# of which block 0 and the local window's (16 positions: 2 or 3 blocks)
CFG = sa.SparseConfig(
    kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8,
    window_size=16, topk=6, dense_len=32,
)


def _qkv(t=128, h=6, hkv=2, d=16, b=2, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(kq, (b, t, h, d), jnp.float32),
        jax.random.normal(kk, (b, t, hkv, d), jnp.float32),
        jax.random.normal(kv, (b, t, hkv, d), jnp.float32),
    )


def brute_choice(q, k, cfg):
    """The rule, one token and group at a time, in numpy float64."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    nb = t // cfg.block_size
    nc = (t - cfg.kernel_size) // cfg.kernel_stride + 1
    out = np.zeros((b, hkv, t, nb), bool)
    for bi in range(b):
        for gi in range(hkv):
            kc = np.stack([
                k[bi, j * cfg.kernel_stride: j * cfg.kernel_stride + cfg.kernel_size, gi].mean(0)
                for j in range(nc)
            ])
            for ti in range(t):
                vis = [j for j in range(nc) if j * cfg.kernel_stride + cfg.kernel_size <= ti + 1]
                sc = np.zeros(nc)
                for hi in range(gi * g, (gi + 1) * g):
                    if vis:
                        z = kc[vis] @ q[bi, ti, hi] / math.sqrt(d)
                        z = np.exp(z - z.max())
                        sc[vis] += z / z.sum()
                score = {}
                for blk in range(ti // cfg.block_size + 1):
                    over = [
                        j for j in vis
                        if j * cfg.kernel_stride < (blk + 1) * cfg.block_size
                        and j * cfg.kernel_stride + cfg.kernel_size > blk * cfg.block_size
                    ]
                    score[blk] = max((sc[j] for j in over), default=-np.inf)
                forced = {
                    blk for blk in score
                    if blk < cfg.init_blocks
                    or (blk + 1) * cfg.block_size - 1 >= ti - cfg.window_size + 1
                }
                rest = sorted(set(score) - forced, key=lambda x: -score[x])
                keep = forced | set(rest[: max(cfg.topk - len(forced), 0)])
                out[bi, gi, ti, sorted(keep)] = True
    return out


def dense_masked(q, k, v, chosen, cfg):
    """Dense attention under the explicit mask ``chosen[block(j)] & j <= t``."""
    b, t, h, d = q.shape
    g = h // k.shape[2]
    keys = np.repeat(np.asarray(chosen), cfg.block_size, axis=-1)
    keys &= np.tril(np.ones((t, t), bool))
    mask = jnp.repeat(jnp.asarray(keys), g, axis=1)  # (B, H, T, T)
    kk, vv = (jnp.repeat(x, g, axis=2) for x in (k, v))
    s = jnp.einsum("bihd,bjhd->bhij", q, kk) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", p, vv)


def _chosen(q, k, cfg=CFG):
    words = sa.select_blocks(q, k, cfg)
    return np.asarray(sa.unpack_blocks(words, q.shape[1] // cfg.block_size))


def test_choice_is_the_rule():
    q, k, _ = _qkv()
    got, want = _chosen(q, k), brute_choice(q, k, CFG)
    np.testing.assert_array_equal(got, want)
    t = np.arange(128)
    n_causal = t // 8 + 1
    # every token keeps min(topk, its causal blocks)
    np.testing.assert_array_equal(got.sum(-1), np.broadcast_to(np.minimum(n_causal, 6), got.shape[:-1]))
    # the init block, the token's own (partial) block and the one before
    assert got[..., 0].all()
    assert got[:, :, t, t // 8].all()
    assert got[:, :, t[t >= 8], t[t >= 8] // 8 - 1].all()
    # nothing after the token's own block
    assert not (got & (np.arange(16)[None, :] > (t // 8)[:, None])).any()


def test_top_k_of_the_rest_follows_the_scores():
    """Raise one far key window's match for one token: that block joins
    the token's choice (and leaves it when the match is lowered)."""
    q, k, _ = _qkv(b=1)
    t, blk = 100, 3  # block 3 is outside 100's local window (blocks 11, 12)
    # keys of block 3 (positions 24..31) aligned with token 100's queries
    k2 = k.at[0, 24:32, 0].set(10 * q[0, t, 0:3].mean(0))
    assert _chosen(q, k2)[0, 0, t, blk]
    k3 = k.at[0, 24:32, 0].set(-10 * q[0, t, 0:3].mean(0))
    assert not _chosen(q, k3)[0, 0, t, blk]


def test_one_choice_for_the_whole_group():
    """Changing ONE head's query changes the group's choice, never the
    other group's: the score is summed over the group's heads."""
    q, k, _ = _qkv(b=1)
    base = _chosen(q, k)
    q2 = q.at[0, 64:, 1].set(-q[0, 64:, 1] * 3)  # head 1: group 0
    moved = _chosen(q2, k)
    np.testing.assert_array_equal(moved[:, 1], base[:, 1])
    assert (moved[:, 0] != base[:, 0]).any()


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_attention_over_the_chosen_keys(impl, monkeypatch):
    monkeypatch.setattr(sa, "_TILE", (16,))  # eight q tiles of 16 tokens
    q, k, v = _qkv()
    o, stats = sa.sparse_attention(q, k, v, CFG, impl=impl)
    want = dense_masked(q, k, v, brute_choice(q, k, CFG), CFG)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=2e-5)
    assert 6 <= float(stats["kv_blocks"]) <= 16


@pytest.mark.parametrize("n, bk", [(16, 8), (16, 16), (32, 8), (32, 16)])
def test_kernels_backward_is_the_oracles(n, bk, monkeypatch):
    """The three interpreted kernels against the ``jnp`` path's written-out
    backward, and both against ``jax.grad`` of the dense masked form, at q
    tiles of 16 and 32 tokens and kv blocks of one and two rule blocks."""
    monkeypatch.setattr(sa, "_tiling", lambda t, block_size: (n, bk))
    q, k, v = _qkv()
    chosen = brute_choice(q, k, CFG)
    # dkv's lists: kv block 0 (every token's init block) holds every tile,
    # the last kv block only the last tile, the rest of its list repeated
    member = sa._membership(sa.select_blocks(q, k, CFG), 128, CFG, n, bk)
    k_lists, k_counts = (
        np.asarray(x) for x in sa._lists(jnp.swapaxes(member, 2, 3))
    )
    assert (k_counts[..., 0] == 128 // n).all() and (k_counts[..., -1] == 1).all()
    assert (k_lists[..., -1, :] == 128 // n - 1).all()
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    want = jax.grad(
        loss(lambda *x: dense_masked(*x, chosen, CFG)), argnums=(0, 1, 2)
    )(q, k, v)
    for impl in ("jnp", "pallas"):
        got = jax.grad(
            loss(lambda *x: sa.sparse_attention(*x, CFG, impl=impl)[0]),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("n, bk", [(16, 8), (16, 16), (32, 8), (32, 16)])
def test_forward_is_the_oracles(n, bk, monkeypatch):
    """The interpreted key-major forward's ``o`` and ``lse`` against the
    ``jnp`` oracle's, at the backward test's tilings: tiles whose lists end
    in repeats past their counts, and steps in which a token keeps no key
    (its row's statistics must pass through such a step unchanged)."""
    monkeypatch.setattr(sa, "_tiling", lambda t, block_size: (n, bk))
    q, k, v = _qkv()
    b, t, h, d = q.shape
    words = sa.select_blocks(q, k, CFG)
    member = sa._membership(words, t, CFG, n, bk)  # (Bsz, Hkv, tile, kv block)
    assert (np.asarray(sa._lists(member)[1]) < t // bk).any()  # repeats
    seen = np.asarray(sa._keys_seen(words, jnp.arange(t), t, CFG)).reshape(
        b, -1, t // n, n, t // bk, bk
    ).any(-1)  # (Bsz, Hkv, tile, token, kv block): the token keeps a key there
    assert seen[..., 0].all()  # every row's first step keeps a key
    assert (np.asarray(member)[:, :, :, None] & ~seen).any()  # steps it keeps none
    args = (
        q.reshape(b, t, h * d), k.reshape(b, t, -1), v.reshape(b, t, -1),
        words, CFG,
    )
    got = sa._forward(*args, "pallas", True)
    want = sa._forward(*args, "jnp", False)
    assert got[1].shape == want[1].shape == (b, h, t)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=2e-5)


def _pallas_calls(jaxpr):
    """``(name, eqn)`` of every ``pallas_call`` in ``jaxpr`` and the jaxprs
    it holds."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, tuple) else (val,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _pallas_calls(sub)


@pytest.mark.parametrize("kernel, counter, stats_shape, n_stats", [
    ("sparse_flash_fwd", "fwd_stats{layout=rows}", (2, 2, 3, 64), 1),
    ("sparse_flash_bwd_dkv", "bwd_stats{layout=rows}", (2, 2, 3, 64), 2),
    ("sparse_flash_bwd_dq", "bwd_stats{layout=columns}", (2, 6, 64, 1), 2),
])
def test_dkv_takes_the_statistics_as_rows(kernel, counter, stats_shape, n_stats):
    """How each Pallas kernel of the sparse path takes or returns its
    float32 statistics, in one trace of the gradient: the key-major forward
    returns ``lse`` and dkv takes ``lse`` and ``delta`` as ``(Bsz, Hkv, G,
    T)`` rows, with no float32 array of a minor dimension of 1 among their
    operands and results (a column padded to 128 lanes); dq takes both as
    ``(Bsz, H, T, 1)`` columns.  Each kernel's layout is counted once."""
    q, k, v = _qkv(t=64)
    c0 = telemetry.counters()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: sa.sparse_attention(q, k, v, CFG, impl="pallas")[0].sum(),
        argnums=(0, 1, 2),
    ))(q, k, v).jaxpr
    c1 = telemetry.counters()
    calls = {name: eqn for name, eqn in _pallas_calls(jaxpr)}
    assert {"sparse_flash_fwd", "sparse_flash_bwd_dq", "sparse_flash_bwd_dkv"} <= set(calls)
    eqn = calls[kernel]
    avals = [x.aval for x in (*eqn.invars, *eqn.outvars)]
    assert [a.shape for a in avals].count(stats_shape) == n_stats
    columns = [a for a in avals if a.dtype == jnp.float32 and a.shape[-1] == 1]
    assert len(columns) == (n_stats if stats_shape[-1] == 1 else 0)
    name = f"sparse_attn.{counter}"
    assert c1[name] == c0.get(name, 0) + 1


def test_kernel_lists_walk_each_tiles_union(monkeypatch):
    """A q tile's list holds exactly the kv blocks some token of it chose,
    ascending, then its last repeated; a kv block's list the tiles."""
    q, k, _ = _qkv()
    words = sa.select_blocks(q, k, CFG)
    n, bk = 16, 16
    member = np.asarray(sa._membership(words, 128, CFG, n, bk))
    lists, counts = (np.asarray(x) for x in sa._lists(jnp.asarray(member)))
    chosen = _chosen(q, k)
    for b in range(2):
        for g in range(2):
            for i in range(8):
                want = sorted({
                    blk // 2 for t in range(i * 16, i * 16 + 16)
                    for blk in np.flatnonzero(chosen[b, g, t])
                })
                c = counts[b, g, i]
                assert c == len(want) and list(lists[b, g, i, :c]) == want
                assert (lists[b, g, i, c:] == want[-1]).all()


def test_the_dense_switch():
    """At ``T <= dense_len`` the layer is plain causal attention, through
    the flash path's dispatch; above, the sparse path."""
    from torchdistx_tpu.ops.attention import attention

    q, k, v = _qkv(t=32)
    c0 = telemetry.counters()
    o, stats = sa.sparse_attention(q, k, v, CFG, impl="jnp", dense_impl="jnp")
    c1 = telemetry.counters()
    assert stats == {}
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(attention(q, k, v, causal=True, impl="jnp")), atol=1e-6
    )
    assert c1["sparse_attn.mode{mode=dense}"] == c0.get("sparse_attn.mode{mode=dense}", 0) + 1
    q, k, v = _qkv(t=40)
    sa.sparse_attention(q, k, v, CFG, impl="pallas")
    c2 = telemetry.counters()
    assert c2["sparse_attn.mode{mode=sparse}"] == c1.get("sparse_attn.mode{mode=sparse}", 0) + 1
    assert c2["sparse_attn{interpret=true}"] > c1.get("sparse_attn{interpret=true}", 0)
    with pytest.raises(ValueError, match="multiple of block_size"):
        sa.sparse_attention(*_qkv(t=44), CFG, impl="jnp")


def test_the_choice_is_kept_for_the_backward():
    """``sparse_choice``, ``sparse_out`` and ``sparse_lse`` are named, and
    the choice has no gradient."""
    q, k, v = _qkv(t=64)
    text = str(jax.make_jaxpr(
        jax.grad(lambda q: sa.sparse_attention(q, k, v, CFG, impl="jnp")[0].sum())
    )(q))
    for name in ("sparse_choice", "sparse_out", "sparse_lse"):
        assert name in text
