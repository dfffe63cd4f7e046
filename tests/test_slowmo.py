"""SlowMo tests — analytic oracles like the reference's
(/root/reference/tests/python/test_slowmo_fsdp.py: rank-distinct gradients via
singleton subgroups, manual averager oracle, closed-form momentum check,
checkpoint round-trip, ctor validation).  Here "rank-distinct" replicas are
the stacked dp axis on a virtual 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from torchdistx_tpu.parallel import MeshSpec, make_mesh
from torchdistx_tpu.parallel.slowmo import (
    SlowMomentumOptimizer,
    load_slowmo_state_dict,
    slowmo_grad_sync,
    slowmo_state_dict,
)

DP = 4


def _stacked_params():
    return {
        "w": jnp.tile(jnp.arange(6.0).reshape(1, 2, 3), (DP, 1, 1)),
        "b": jnp.ones((DP, 3)),
    }


def _distinct_grads():
    # Each replica gets a different gradient (the reference's singleton-
    # subgroup trick, test_slowmo_fsdp.py:119-131).
    return {
        "w": jnp.stack([jnp.full((2, 3), float(r + 1)) for r in range(DP)]),
        "b": jnp.stack([jnp.full((3,), 0.1 * (r + 1)) for r in range(DP)]),
    }


def test_replicas_diverge_then_average():
    lr = 0.1
    opt = SlowMomentumOptimizer(
        optax.sgd(lr), base_lr=lr, slowmo_freq=3, slowmo_factor=0.0,
        slowmo_lr=1.0,
    )
    params = _stacked_params()
    state = opt.init(params)
    grads = _distinct_grads()
    for step in range(1, 4):
        params, state = opt.update(grads, state, params)
        replicas = np.asarray(params["w"])
        if step < 3:
            assert not np.allclose(replicas[0], replicas[1])
        else:
            for r in range(1, DP):
                np.testing.assert_allclose(replicas[0], replicas[r])


def test_momentum_math_closed_form():
    # Analytic oracle (slowmo_optimizer.py:206-227 math; reference test
    # recomputes it the same way, test_slowmo_fsdp.py:243-253).
    lr, freq, alpha, slr = 0.1, 2, 0.5, 0.7
    opt = SlowMomentumOptimizer(
        optax.sgd(lr), base_lr=lr, slowmo_freq=freq, slowmo_factor=alpha,
        slowmo_lr=slr,
    )
    params = _stacked_params()
    p0 = np.asarray(params["w"][0])  # initial (same on all replicas)
    state = opt.init(params)
    grads = _distinct_grads()
    g = np.asarray(grads["w"])

    # two steps of local SGD then averaging:
    local = np.asarray(params["w"]) - 2 * lr * g
    avg = local.mean(axis=0)
    m = 0.0 * alpha + (p0 - avg) / lr
    prev = p0 - slr * lr * m
    params, state = opt.update(grads, state, params)
    params, state = opt.update(grads, state, params)
    for r in range(DP):
        np.testing.assert_allclose(
            np.asarray(params["w"][r]), prev, rtol=1e-5
        )
    np.testing.assert_allclose(np.asarray(state.momentum["w"]), m, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state.prev["w"]), prev, rtol=1e-5)


def test_momentum_accumulates_across_cycles():
    lr, freq, alpha = 0.1, 1, 0.5
    opt = SlowMomentumOptimizer(
        optax.sgd(lr), base_lr=lr, slowmo_freq=freq, slowmo_factor=alpha,
        slowmo_lr=1.0,
    )
    params = _stacked_params()
    state = opt.init(params)
    grads = _distinct_grads()
    params, state = opt.update(grads, state, params)
    m1 = np.asarray(state.momentum["w"])
    params, state = opt.update(grads, state, params)
    m2 = np.asarray(state.momentum["w"])
    # m2 = alpha*m1 + (prev1 - avg2)/lr, with nonzero m1 -> not equal.
    assert not np.allclose(m1, m2)
    assert np.abs(m2).max() > 0


def test_under_jit_on_mesh():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(MeshSpec(dp=4, tp=2))
    lr = 0.05
    opt = SlowMomentumOptimizer(
        optax.sgd(lr), base_lr=lr, slowmo_freq=2, slowmo_factor=0.3,
        slowmo_lr=1.0,
    )
    params = _stacked_params()
    state = opt.init(params)
    grads = _distinct_grads()

    shard = NamedSharding(mesh, P("dp"))
    params_sharded = jax.tree.map(lambda p: jax.device_put(p, shard), params)
    grads_sharded = jax.tree.map(lambda g: jax.device_put(g, shard), grads)

    step = jax.jit(opt.update)
    p1, s1 = step(grads_sharded, state, params_sharded)
    p2, s2 = step(grads_sharded, s1, p1)
    # Oracle: same math unjitted/unsharded.
    q1, t1 = opt.update(grads, state, params)
    q2, t2 = opt.update(grads, t1, q1)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(q2["w"]), rtol=1e-6)
    # Sharding preserved across the step.
    assert p2["w"].sharding.spec == shard.spec


def test_works_with_adam():
    lr = 0.01
    opt = SlowMomentumOptimizer(
        optax.adam(lr), base_lr=lr, slowmo_freq=2, slowmo_factor=0.5,
        slowmo_lr=1.0,
    )
    params = _stacked_params()
    state = opt.init(params)
    grads = _distinct_grads()
    for _ in range(4):
        params, state = opt.update(grads, state, params)
    assert np.isfinite(np.asarray(params["w"])).all()
    r = np.asarray(params["w"])
    for k in range(1, DP):
        np.testing.assert_allclose(r[0], r[k], rtol=1e-6)


def test_training_converges():
    # End-to-end: fit y = x @ w on dp-sharded batches; loss must drop.
    key = jax.random.PRNGKey(0)
    true_w = jax.random.normal(key, (8, 1))
    x = jax.random.normal(jax.random.fold_in(key, 1), (DP, 64, 8))
    y = x @ true_w

    params = {"w": jnp.zeros((DP, 8, 1))}
    lr = 0.1
    opt = SlowMomentumOptimizer(
        optax.sgd(lr), base_lr=lr, slowmo_freq=4, slowmo_factor=0.5,
        slowmo_lr=1.0,
    )
    state = opt.init(params)

    def replica_loss(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    @jax.jit
    def train_step(params, state, x, y):
        loss, grads = jax.vmap(jax.value_and_grad(replica_loss))(
            params["w"], x, y
        )
        params, state = opt.update({"w": grads}, state, params)
        return params, state, loss.mean()

    losses = []
    for _ in range(60):
        params, state, loss = train_step(params, state, x, y)
        losses.append(float(loss))
    assert losses[-1] < 0.05 * losses[0]


def test_ctor_validation():
    # Reference test_slowmo_fsdp.py:326-364.
    with pytest.raises(ValueError, match="slowmo_freq"):
        SlowMomentumOptimizer(optax.sgd(0.1), base_lr=0.1, slowmo_freq=0)
    with pytest.raises(ValueError, match="slowmo_factor"):
        SlowMomentumOptimizer(
            optax.sgd(0.1), base_lr=0.1, slowmo_factor=-1.0
        )
    with pytest.raises(ValueError, match="slowmo_lr"):
        SlowMomentumOptimizer(optax.sgd(0.1), base_lr=0.1, slowmo_lr=-0.1)
    with pytest.raises(ValueError, match="base_lr"):
        SlowMomentumOptimizer(optax.sgd(0.1), base_lr=0.0)


def test_state_dict_roundtrip():
    # Reference test_slowmo_fsdp.py:255-324.
    lr = 0.1
    opt = SlowMomentumOptimizer(
        optax.sgd(lr), base_lr=lr, slowmo_freq=3, slowmo_factor=0.5,
        slowmo_lr=2.0,
    )
    params = _stacked_params()
    state = opt.init(params)
    grads = _distinct_grads()
    for _ in range(3):
        params, state = opt.update(grads, state, params)
    d = slowmo_state_dict(opt, state)
    assert d["slowmo_freq"] == 3 and d["step"] == 3

    opt2 = SlowMomentumOptimizer(
        optax.sgd(lr), base_lr=lr, slowmo_freq=99
    )
    state2 = load_slowmo_state_dict(opt2, d)
    assert opt2.slowmo_freq == 3 and opt2.slowmo_lr == 2.0
    p_a, s_a = opt.update(grads, state, params)
    p_b, s_b = opt2.update(grads, state2, params)
    np.testing.assert_allclose(
        np.asarray(p_a["w"]), np.asarray(p_b["w"]), rtol=1e-7
    )


def test_state_dict_missing_key():
    opt = SlowMomentumOptimizer(optax.sgd(0.1), base_lr=0.1)
    d = slowmo_state_dict(opt, opt.init(_stacked_params()))
    del d["base_lr"]
    with pytest.raises(ValueError, match="base_lr"):
        load_slowmo_state_dict(opt, d)


def test_grad_sync_hook():
    # slowmo_comm parity: pmean over an explicit intra axis in shard_map.
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = make_mesh(MeshSpec(dp=2, tp=4))
    g = jnp.arange(8.0).reshape(2, 4)

    def f(g):
        return slowmo_grad_sync(g, axis_name="tp")

    out = shard_map(
        f, mesh=mesh, in_specs=P("dp", "tp"), out_specs=P("dp", "tp")
    )(g)
    expected = np.tile(g.mean(axis=1, keepdims=True), (1, 4))
    np.testing.assert_allclose(np.asarray(out), expected)

    out2 = shard_map(
        lambda g: slowmo_grad_sync(g, axis_name="tp", enabled=False),
        mesh=mesh, in_specs=P("dp", "tp"), out_specs=P("dp", "tp"),
    )(g)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(g))
