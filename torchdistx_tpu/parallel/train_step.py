"""Sharded training steps: pjit over a named mesh, FSDP/TP/DP/SP + SlowMo.

The reference framework's training story is SlowMo over FSDP ``NO_SHARD``
replicas wired through torch.distributed process groups
(/root/reference/src/python/torchdistx/slowmo/).  The TPU-native story is a
single jitted SPMD program over a ``jax.sharding.Mesh``:

* **FSDP/TP** — parameters placed by :func:`models.llama.param_specs`; XLA's
  SPMD partitioner inserts the all-gathers/reduce-scatters (ZeRO-3) and the
  Megatron psums (TP).  No wrapper classes, no hooks.
* **DP** — the batch dim is sharded over the data axes; gradient all-reduce
  is just autodiff of the sharded loss mean.
* **SP** — ``seq_axis`` routes attention through ring attention
  (:mod:`torchdistx_tpu.parallel.ring_attention`).
* **SlowMo** — :func:`make_slowmo_train_step` keeps *diverging* replicas as
  a stacked leading ``dp`` axis (vmapped forward), with the periodic exact
  averaging lowering to one all-reduce over the DCN-major ``dp`` axis — the
  intra-node/inter-node split of the reference mapped onto ICI/DCN
  (SURVEY.md §2.3).

All state lives in an explicit :class:`TrainState` pytree (orbax-
checkpointable; see :mod:`torchdistx_tpu.utils.checkpoint`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import llama
from ..resilience import guard as _guard
from ..telemetry import perf as _perf
from .sharding import fit_shardings
from .slowmo import SlowMomentumOptimizer, SlowMoState

__all__ = [
    "TrainState",
    "make_train_step",
    "make_slowmo_train_step",
    "batch_sharding",
]


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: Any  # scalar int32


def _named(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def batch_sharding(mesh, *, data_axes=("dp", "fsdp")) -> NamedSharding:
    """Sharding for ``(B, S)`` token batches: batch dim over the data axes."""
    present = tuple(a for a in data_axes if a in mesh.axis_names)
    return _named(mesh, P(present or None, None))


def _match_param_shardings(mesh, params_abstract, param_shardings, target):
    """Sharding for an arbitrary state pytree (optimizer moments etc.).

    Optax moment trees (adam's mu/nu, sgd's trace, ...) embed the *params
    tree structure*, so a state leaf whose tree-path suffix + shape match a
    parameter leaf inherits that parameter's sharding.  Matching by shape
    alone is wrong: wq ``(L, D, D)`` and wo ``(L, D, D)`` collide while
    their shardings are transposed.  Shape matching remains only as a
    fallback when it is unambiguous; everything else (counts, scalars)
    replicates.
    """
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    p_leaves, _ = tree_flatten_with_path(params_abstract)
    s_leaves = jax.tree.leaves(param_shardings)
    by_path = {}
    by_shape = {}
    for (path, leaf), sh in zip(p_leaves, s_leaves):
        keys = tuple(str(k) for k in path)
        by_path[keys] = (leaf.shape, sh)
        by_shape.setdefault(leaf.shape, set()).add(sh)
    suffix_lens = sorted({len(p) for p in by_path}, reverse=True)
    rep = _named(mesh, P())

    t_leaves, treedef = tree_flatten_with_path(target)
    out = []
    for path, leaf in t_leaves:
        keys = tuple(str(k) for k in path)
        shape = getattr(leaf, "shape", None)
        placed = None
        for n in suffix_lens:
            hit = by_path.get(keys[-n:]) if n <= len(keys) else None
            if hit is not None and hit[0] == shape:
                placed = hit[1]
                break
        if placed is None and shape in by_shape and len(by_shape[shape]) == 1:
            placed = next(iter(by_shape[shape]))
        out.append(placed or rep)
    return tree_unflatten(treedef, out)


def make_train_step(
    cfg,
    mesh,
    tx,
    *,
    model=llama,
    tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
    seq_axis: Optional[str] = None,
    pp_axis: Optional[str] = None,
    n_microbatches: int = 1,
    pp_schedule: str = "gpipe",
    attn_impl: str = "auto",
    seq_layout: str = "contiguous",
    loss_fn: Optional[Callable] = None,
    nonfinite_guard: bool = True,
) -> Tuple[Callable, Callable]:
    """Build ``(init_fn, step_fn)`` for standard optax training.

    ``model`` is a model family module implementing the protocol
    ``init_params(key, cfg)`` / ``abstract_params(cfg)`` /
    ``param_specs(cfg, tp=, fsdp=)`` / ``loss_fn(params, tokens, targets,
    cfg, ...)`` — :mod:`torchdistx_tpu.models.llama` (default) and
    :mod:`~torchdistx_tpu.models.gpt2` both qualify.  A family that sets
    ``LOSS_HAS_AUX`` returns ``(loss, aux)`` from ``loss_fn``; ``aux`` (a
    dict of device values, e.g. ``{"moe": routing counts}`` of
    :mod:`~torchdistx_tpu.models.deepseek_v3`) is merged into the step's
    ``metrics`` without a host sync.

    ``init_fn(key) -> TrainState`` — shard-then-materialize: parameters are
    initialized by one compiled program whose ``out_shardings`` place every
    shard on its own device (no full tensor anywhere).

    ``step_fn(state, batch) -> (state, metrics)`` — one jitted SPMD training
    step; ``batch`` is ``{"tokens": (B,S), "targets": (B,S)}`` sharded with
    :func:`batch_sharding`.  State buffers are donated.  What is returned
    is the jitted function as the compile observatory's tracked program
    ``train_step`` (:class:`telemetry.perf.JitProgram`: call it, ``.lower``
    it; ``compile.count{program=train_step}`` counts its compiles, and with
    a span sink active each compile records its scope map).

    ``nonfinite_guard`` (default on) adds a jit-side all-reduced
    finiteness check over loss and gradients: a poisoned step returns
    the PRIOR state bit-identical (params, optimizer moments, and step
    counter all unchanged — one NaN gradient must not corrupt optimizer
    state forever) and reports ``metrics["nonfinite"]=True`` so the
    training loop can count skips and escalate (see
    :mod:`torchdistx_tpu.resilience.guard`).  A clean step's update is
    unaffected — the select picks the freshly computed state.  The
    reserved batch key ``_tdx_nan`` (injected by ``fit()`` under a
    ``TDX_FAULT=step.exec:N:nan`` spec) deterministically poisons the
    loss for fault-injection tests.

    ``pp_schedule``: ``"gpipe"`` (autodiff through the pipeline scan) or
    ``"1f1b"`` (hand-written interleaved backward with O(P) live
    activations — :func:`parallel.pipeline.pipeline_value_and_grad`;
    requires a model family exposing ``pp_value_and_grad``, e.g. llama).
    """
    # pp kwargs are only passed when pipeline parallelism is requested, so
    # custom model families implementing the base protocol
    # (param_specs(cfg, *, tp, fsdp); loss_fn without pp kwargs) still work.
    pp_spec_kw = {"pp": pp_axis} if pp_axis is not None else {}
    pp_loss_kw = (
        {"pp_axis": pp_axis, "n_microbatches": n_microbatches}
        if pp_axis is not None
        else {}
    )
    specs = model.param_specs(cfg, tp=tp, fsdp=fsdp, **pp_spec_kw)
    abstract = model.abstract_params(cfg)
    param_shardings = fit_shardings(specs, abstract, mesh)
    # Only forwarded when non-default, so model families without the kwarg
    # (gpt2/moe) keep working with the base protocol.
    layout_kw = (
        {"seq_layout": seq_layout} if seq_layout != "contiguous" else {}
    )
    if loss_fn is not None and seq_layout != "contiguous":
        # The layout is applied inside the model's own loss_fn (token
        # permutation + target alignment); it cannot be injected into a
        # user-provided loss, so silently ignoring it would train on a
        # contiguous layout the caller did not ask for.
        raise ValueError(
            f"seq_layout={seq_layout!r} cannot be combined with a custom "
            "loss_fn — apply the layout inside your loss_fn and pass "
            "seq_layout='contiguous'."
        )
    _loss = loss_fn or functools.partial(
        model.loss_fn, cfg=cfg, mesh=mesh, seq_axis=seq_axis,
        attn_impl=attn_impl, **pp_loss_kw, **layout_kw,
    )
    has_aux = loss_fn is None and getattr(model, "LOSS_HAS_AUX", False)

    if pp_schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pp_schedule: {pp_schedule!r}")
    value_and_grad = None
    if pp_schedule == "1f1b":
        if pp_axis is None:
            raise ValueError("pp_schedule='1f1b' requires pp_axis=")
        if loss_fn is not None:
            raise ValueError(
                "pp_schedule='1f1b' computes the loss inside the pipeline "
                "and cannot wrap a custom loss_fn"
            )
        if seq_axis is not None or seq_layout != "contiguous":
            # pp_pieces has no sequence-parallel path; silently training on
            # a contiguous layout would diverge from the same call under
            # pp_schedule='gpipe'.
            raise ValueError(
                "pp_schedule='1f1b' does not compose with seq_axis/"
                "seq_layout — use pp_schedule='gpipe' for sp×pp"
            )
        if not hasattr(model, "pp_value_and_grad"):
            raise ValueError(
                f"pp_schedule='1f1b' requires {model.__name__} to expose "
                "pp_value_and_grad (see models.llama / models.gpt2)"
            )
        value_and_grad = functools.partial(
            model.pp_value_and_grad, cfg=cfg, mesh=mesh, pp_axis=pp_axis,
            n_microbatches=n_microbatches, attn_impl=attn_impl,
        )

    opt_abstract = jax.eval_shape(tx.init, abstract)
    opt_shardings = _match_param_shardings(
        mesh, abstract, param_shardings, opt_abstract
    )
    state_shardings = TrainState(
        params=param_shardings,
        opt_state=opt_shardings,
        step=_named(mesh, P()),
    )

    @functools.partial(jax.jit, out_shardings=state_shardings)
    def init_fn(key):
        params = model.init_params(key, cfg)
        return TrainState(
            params=params,
            opt_state=tx.init(params),
            step=jnp.zeros((), jnp.int32),
        )

    @functools.partial(
        jax.jit, out_shardings=(state_shardings, None), donate_argnums=(0,)
    )
    def step_fn(state: TrainState, batch):
        # loss / optimizer / guard: named_scope regions (HLO metadata
        # only) by which a profile's device time is read; the model
        # families add embed / attn / mlp / head under loss.
        aux = {}
        with jax.named_scope("loss"):
            if value_and_grad is not None:
                loss, grads = value_and_grad(
                    state.params, batch["tokens"], batch["targets"]
                )
            else:
                loss, grads = jax.value_and_grad(_loss, has_aux=has_aux)(
                    state.params, batch["tokens"], batch["targets"]
                )
                if has_aux:
                    loss, aux = loss
        if "_tdx_nan" in batch:
            # Deterministic fault injection (resilience.faults, kind
            # "nan"): poison the loss so the guard's real detection path
            # trips — the key only exists on injected calls, so clean
            # steps compile without this select.
            loss = jnp.where(
                jnp.asarray(batch["_tdx_nan"]),
                jnp.asarray(jnp.nan, dtype=loss.dtype),
                loss,
            )
        import optax

        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        new_state = TrainState(params, opt_state, state.step + 1)
        if nonfinite_guard:
            with jax.named_scope("guard"):
                ok = _guard.tree_allfinite(loss, grads)
                new_state = _guard.select_tree(ok, new_state, state)
            metrics = {
                "loss": loss,
                "step": new_state.step,
                "nonfinite": ~ok,
            }
        else:
            metrics = {"loss": loss, "step": new_state.step}
        return new_state, {**aux, **metrics}

    _perf.install_monitoring()
    return init_fn, _perf.JitProgram(
        lambda: step_fn, "train_step", scopes=True
    )


# ---------------------------------------------------------------------------
# SlowMo training step (stacked-replica DP over the dp axis)


def make_slowmo_train_step(
    cfg,
    mesh,
    opt: SlowMomentumOptimizer,
    *,
    model=llama,
    dp_axis: str = "dp",
    tp: Optional[str] = "tp",
    fsdp: Optional[str] = "fsdp",
    attn_impl: str = "auto",
) -> Tuple[Callable, Callable]:
    """Build ``(init_fn, step_fn)`` for SlowMo training.

    Replicas that diverge between averaging steps are a stacked leading axis
    of size ``mesh.shape[dp_axis]`` on every parameter leaf, sharded over
    ``dp_axis`` — each replica trains on its own batch shard with its own
    base-optimizer state; every ``slowmo_freq`` steps the ``lax.cond`` branch
    runs the exact averaging (one all-reduce over DCN) + slow-momentum
    update.  Within a replica, fsdp/tp shard the *trailing* dims as usual.

    ``step_fn(state, batch)`` takes ``batch`` ``{"tokens","targets"}`` of
    shape ``(dp, B, S)`` sharded ``P("dp", fsdp-axes, None)``.
    """
    ndp = mesh.shape[dp_axis]
    specs = jax.tree.map(
        lambda s: P(dp_axis, *s),
        model.param_specs(cfg, tp=tp, fsdp=fsdp),
        is_leaf=lambda x: isinstance(x, P),
    )
    abstract = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((ndp,) + l.shape, l.dtype),
        model.abstract_params(cfg),
    )
    stacked_shardings = fit_shardings(specs, abstract, mesh)
    state_abstract = jax.eval_shape(opt.init, abstract)
    # prev/momentum are unstacked (replica-shared); base state is stacked.
    unstacked_shardings = jax.tree.map(
        lambda sh: _named(mesh, P(*sh.spec[1:])), stacked_shardings
    )
    opt_shardings = SlowMoState(
        base=_match_param_shardings(
            mesh, abstract, stacked_shardings, state_abstract.base
        ),
        prev=unstacked_shardings,
        momentum=unstacked_shardings,
        step=_named(mesh, P()),
    )
    state_shardings = TrainState(
        params=stacked_shardings, opt_state=opt_shardings, step=_named(mesh, P())
    )

    # The per-replica loss runs under vmap over the stacked dp axis, where
    # neither the flash kernel's shard_map wrapper (its dp batch spec would
    # split a replica's local batch across the axis replicas diverge over)
    # nor the bare Mosaic kernel (no SPMD rules) can run — pin "auto" to
    # XLA's jnp attention and refuse an explicit "pallas".
    if attn_impl == "pallas":
        raise ValueError(
            "attn_impl='pallas' is not supported in the SlowMo step (the "
            "loss is vmapped over stacked replicas); use 'auto' or 'jnp'"
        )
    resolved_impl = "jnp" if attn_impl == "auto" else attn_impl

    def _loss(params, tokens, targets):
        # mesh is forwarded for ring/seq-parallel dispatch decisions.
        return model.loss_fn(
            params, tokens, targets, cfg, mesh=mesh, attn_impl=resolved_impl
        )

    @functools.partial(jax.jit, out_shardings=state_shardings)
    def init_fn(key):
        params = model.init_params(key, cfg)
        stacked = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (ndp,) + p.shape), params
        )
        return TrainState(
            params=stacked,
            opt_state=opt.init(stacked),
            step=jnp.zeros((), jnp.int32),
        )

    @functools.partial(
        jax.jit, out_shardings=(state_shardings, None), donate_argnums=(0,)
    )
    def step_fn(state: TrainState, batch):
        # Per-replica loss/grads — the vmap axis IS the dp axis.
        with jax.named_scope("loss"):
            losses, grads = jax.vmap(jax.value_and_grad(_loss))(
                state.params, batch["tokens"], batch["targets"]
            )
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(
                grads, state.opt_state, state.params
            )
        metrics = {"loss": losses.mean(), "step": state.step + 1}
        return TrainState(params, opt_state, state.step + 1), metrics

    _perf.install_monitoring()
    return init_fn, _perf.JitProgram(
        lambda: step_fn, "train_step_slowmo", scopes=True
    )


def slowmo_batch_sharding(mesh, *, dp_axis="dp", data_axes=("fsdp",)):
    present = tuple(a for a in data_axes if a in mesh.axis_names)
    return _named(mesh, P(dp_axis, present or None, None))
