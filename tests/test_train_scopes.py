"""The train step's named scopes and its place in the compile observatory
(ISSUE 25): ``embed`` / ``attn`` / ``mlp`` / ``head`` / ``optimizer`` /
``guard`` reach the optimized program's ``op_name`` metadata and change
nothing else; the flash kernels carry fixed names; the step is the tracked
program ``train_step``; with a span sink its compile records a scope map,
and without one nothing extra is lowered or compiled.  ISSUE 35: in all
five families; every scan over layers is under ``stack``, what a scan's
transpose generates has that name and no block's; ``attn`` is split into
``norm`` / ``proj_in`` / ``rope`` / ``concat`` / ``proj_out``, and the
flash kernels' doors are ``attn/relayout`` and ``attn/delta``."""

import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import afmoe, deepseek_v3, gpt2, jamba, llama
from torchdistx_tpu.ops.pallas.flash_attention import flash_attention
from torchdistx_tpu.parallel import train_step as ts
from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh
from torchdistx_tpu.parallel.slowmo import SlowMomentumOptimizer
from torchdistx_tpu.telemetry import perf

SCOPES = ("embed", "attn", "mlp", "head", "optimizer", "guard")
# A block's own scopes: what the scans' own work must NOT carry.
BLOCKS = {"embed", "attn", "mlp", "moe", "mamba", "head"}


def _afmoe_two_periods():
    """``afmoe_test`` with its expert stack two whole periods long, so
    that the stack's scan is a loop."""
    return dataclasses.replace(
        afmoe.afmoe_test(), n_moe_layers=4,
        layer_types=(
            afmoe.WINDOW, afmoe.WINDOW, afmoe.FULL, afmoe.WINDOW, afmoe.FULL
        ),
    )


FAMILIES = {
    "gpt2": (gpt2, gpt2.gpt2_test),
    "llama": (llama, llama.llama_test),
    "deepseek_v3": (deepseek_v3, deepseek_v3.deepseek_v3_test),
    "jamba": (jamba, jamba.jamba_test),
    "afmoe": (afmoe, _afmoe_two_periods),
}
# The sub-scopes of ``attn`` each family's block opens itself.
ATTN = {
    "gpt2": {"norm", "proj_in", "proj_out"},
    "llama": {"norm", "proj_in", "rope", "proj_out"},
    "deepseek_v3": {"norm", "proj_in", "rope", "concat", "proj_out"},
    "jamba": {"norm", "proj_in", "proj_out"},
    "afmoe": {"norm", "proj_in", "qk_norm", "rope", "gate", "proj_out"},
}


def _scanned(family):
    """The family's test config with its layers under ``lax.scan`` and
    remat, as at full depth."""
    model, make = FAMILIES[family]
    cfg = make()
    unroll = {"layer_unroll": 1} if hasattr(cfg, "layer_unroll") else {}
    return model, dataclasses.replace(cfg, remat=True, **unroll)


def _step(family, tx=None, **kw):
    model, cfg = _scanned(family)
    mesh = make_mesh(MeshSpec(fsdp=2), devices=jax.devices()[:2])
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, tx or optax.adamw(1e-3), model=model, **kw
    )
    return cfg, mesh, init_fn, step_fn


@functools.lru_cache(maxsize=None)
def _paths(family, attn_impl="auto"):
    """Every ``op_name`` path of the family's optimized train step (one
    compile a family for the tests that only read paths)."""
    cfg, mesh, init_fn, step_fn = _step(family, attn_impl=attn_impl)
    state = init_fn(jax.random.PRNGKey(0))
    text = step_fn.lower(state, _batch(cfg, mesh)).compile().as_text()
    return frozenset(
        p for ps in perf.hlo_scopes(text).values() for p in ps if p
    )


def _batch(cfg, mesh, seq=32):
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, seq), 0, cfg.vocab_size),
        ts.batch_sharding(mesh),
    )
    return {"tokens": tokens, "targets": tokens}


def _count(name):
    return telemetry.counters().get(name, 0)


def _compiles():
    return {
        k: v for k, v in telemetry.counters().items()
        if k.startswith("compile.count{")
    }


def _grew(before):
    return {
        k: v - before.get(k, 0) for k, v in _compiles().items()
        if v > before.get(k, 0)
    }


def _chain(path):
    """The innermost word of each component, in order:
    ``loss/transpose(jvp(attn))/mul`` -> ``[loss, attn, mul]``."""
    return [
        (re.findall(r"[A-Za-z_]\w*", c) or [""])[-1] for c in path.split("/")
    ]


def _words(path):
    return set(_chain(path))


@contextlib.contextmanager
def _sink():
    prev = telemetry.configure(collect=True)
    try:
        yield
    finally:
        telemetry.configure(**prev)


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")


_NAME = re.compile(r"%([^\s=(),{}]+?)(?:\.\d+)?(?=[\s=(),{}]|$)")


def _bare(hlo_text):
    """Optimized HLO without the tables of source locations at its head,
    without any instruction's metadata, and with every name's number that
    of its first appearance among the names of its stem: XLA numbers the
    instructions it names alike in an order that follows their metadata
    (the ``deepseek_v3`` step's ``broadcast_in_dim.N`` shift by 19)."""
    body = hlo_text[hlo_text.index("\n\n%") if "\n\n%" in hlo_text else 0:]
    seen, stems = {}, {}

    def renumber(m):
        if m.group(0) not in seen:
            n = stems[m.group(1)] = stems.get(m.group(1), 0) + 1
            seen[m.group(0)] = f"%{m.group(1)}.{n}"
        return seen[m.group(0)]

    return _NAME.sub(renumber, _METADATA.sub("", body))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_compiled_step_carries_the_six_scopes(family):
    paths = _paths(family)
    assert set().union(*map(_words, paths)) & set(SCOPES) == set(SCOPES)
    # the backward pass keeps the names, wrapped by autodiff
    assert any("transpose(" in p and "mlp" in _words(p) for p in paths)
    assert any("rematted_computation" in p and "attn" in _words(p) for p in paths)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scopes_are_metadata_only(family, monkeypatch):
    """The optimized program built with every ``jax.named_scope`` a no-op
    is, metadata aside, the program built with them."""
    cfg, mesh, init_fn, step_fn = _step(family)
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(cfg, mesh)
    scoped = step_fn.lower(state, batch).compile().as_text()
    assert "/optimizer/" in scoped
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )
    _, _, _, plain_fn = _step(family)
    plain = plain_fn.lower(state, batch).compile().as_text()
    assert "/optimizer/" not in plain
    assert _bare(scoped) == _bare(plain)


_SCAN_OWN = re.compile(r"/while/body/dynamic_(?:update_)?slice$")


def _under_attn(paths, name):
    """The paths that hold ``name`` somewhere under ``attn``."""
    chains = ((p, _chain(p)) for p in paths)
    return [p for p, c in chains if name in c[c.index("attn"):]]


def _both_ways(paths):
    """Forward and in the backward pass."""
    return any("transpose(" not in p for p in paths) and any(
        "transpose(" in p for p in paths
    )


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_what_a_scans_transpose_generates_is_under_stack(family):
    """A layer's weights and residuals sliced out of their stacks and the
    stacked gradients updated in the backward loop: every such instruction
    outside a block has ``stack`` as its innermost name."""
    own = [
        p for p in _paths(family)
        if "transpose(" in p and _SCAN_OWN.search(p)
        and not _words(p) & BLOCKS
    ]
    assert {p.rsplit("/", 1)[1] for p in own} == {
        "dynamic_slice", "dynamic_update_slice"
    }
    for p in own:
        assert _chain(p[: _SCAN_OWN.search(p).start()])[-1] == "stack", p


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_attn_is_split_forward_and_backward(family):
    paths = _paths(family)
    attn = [p for p in paths if "attn" in _words(p)]
    for name in ATTN[family]:
        assert _both_ways(_under_attn(attn, name)), name
    # nothing of a block is left outside a block-level scope: a path of a
    # loop's body with no block's name is the scan's own, and no scan
    # multiplies matrices or takes a norm
    for p in paths:
        if "/while/body/" in p and not _words(p) & BLOCKS:
            assert "stack" in _words(p), p
            assert not re.search(r"/(dot_general|rsqrt|reduce_sum|exp)$", p), p


def test_flash_step_has_relayout_and_delta_under_attn():
    """The transposes at the kernels' doors and ``delta``'s row sum, in a
    step whose attention is the flash kernels (interpreted here), beside
    the five names latent attention's block opens itself."""
    paths = [
        p for p in _paths("deepseek_v3", "pallas")
        if "attn" in _words(p)
    ]
    for name in ATTN["deepseek_v3"] | {"relayout"}:
        assert _both_ways(_under_attn(paths, name)), name
    delta = _under_attn(paths, "delta")
    assert delta and all("transpose(" in p for p in delta)


@pytest.mark.parametrize(
    "seq, names",
    [
        (1024, ["flash_fwd", "flash_bwd_fused"]),
        # Several kv blocks, dq within its VMEM budget: still one kernel
        (4096, ["flash_fwd", "flash_bwd_fused"]),
        (65536, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ],
)
def test_flash_kernels_have_fixed_names(seq, names):
    """Lowered for the TPU (Mosaic's lowering needs no chip): each
    ``pallas_call`` is a ``tpu_custom_call`` under its own fixed name."""

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, seq, 2, 64), jnp.bfloat16)
    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .trace(x, x, x)
        .lower(lowering_platforms=("tpu",))
        .as_text(debug_info=True)
    )
    assert text.count("tpu_custom_call") >= len(names)
    for name in names:
        assert f'kernel_name = "{name}"' in text or f"/{name}/" in text, name
    assert sorted(set(re.findall(r"flash_(?:fwd|bwd_\w+?)\b", text))) == sorted(
        names
    )


def test_train_step_is_a_tracked_program():
    cfg, mesh, init_fn, step_fn = _step("gpt2")
    c0 = _count("compile.count{program=train_step}")
    r0 = _count("compile.recompiles{program=train_step}")
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(cfg, mesh)
    for _ in range(3):
        before = state
        state, metrics = step_fn(state, batch)
    assert before.params["wte"]["weight"].is_deleted()  # still donated
    assert int(metrics["step"]) == 3
    assert _count("compile.count{program=train_step}") - c0 == 1
    assert _count("compile.recompiles{program=train_step}") - r0 == 0
    state, _ = step_fn(state, _batch(cfg, mesh, seq=64))  # a new shape
    assert _count("compile.count{program=train_step}") - c0 == 2
    assert _count("compile.recompiles{program=train_step}") - r0 == 1
    hist = telemetry.histograms()["compile.time_s{program=train_step}"]
    assert hist["count"] >= 2 and hist["sum"] > 0
    # a second train step in the process is another program, not a
    # recompile of the first
    cfg, mesh, init_fn, other = _step("gpt2")
    other(init_fn(jax.random.PRNGKey(0)), batch)
    assert _count("compile.count{program=train_step}") - c0 == 3
    assert _count("compile.recompiles{program=train_step}") - r0 == 1


def test_no_sink_no_scope_map_and_one_compile():
    telemetry.reset()
    assert not telemetry.enabled()
    cfg, mesh, init_fn, step_fn = _step("gpt2")
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(cfg, mesh)
    before = _compiles()
    for _ in range(2):
        state, _ = step_fn(state, batch)
    # the step was lowered and compiled once, and nothing else was
    assert _grew(before) == {"compile.count{program=train_step}": 1}
    assert perf.program_scopes() == {}


def test_sink_records_a_scope_map_of_every_instruction():
    telemetry.reset()
    cfg, mesh, init_fn, step_fn = _step("llama")
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(cfg, mesh)
    text = step_fn.lower(state, batch).compile().as_text()
    before = _compiles()
    with _sink():
        for _ in range(2):
            state, _ = step_fn(state, batch)
        spans = [
            r for r in telemetry.snapshot()["spans"]
            if r.get("name") == "perf.scope_map"
        ]
    scope_map = perf.program_scopes()["train_step"]
    names = set(re.findall(r"^\s+(?:ROOT\s+)?%?([^\s=(]+)\s*=\s", text, re.M))
    assert len(names) > 100 and names <= set(scope_map)
    assert all(
        isinstance(v, tuple) and all(isinstance(p, str) for p in v)
        for v in scope_map.values()
    )
    # recorded once, at the one compile, which stays the only one: the map
    # is read off the executable JAX already holds
    assert len(spans) == 1 and spans[0]["attrs"]["program"] == "train_step"
    assert _grew(before) == {"compile.count{program=train_step}": 1}
    telemetry.reset()
    assert perf.program_scopes() == {}


def test_hlo_scopes_lists_what_a_fusion_holds():
    text = """HloModule jit_f, is_scheduled=true

%inner (p1: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  ROOT %add.7 = f32[8]{0} add(%p1, %p1), metadata={op_name="jit(f)/moe/router/add_any"}
}

%outer (p2: f32[8]) -> f32[8] {
  %p2 = f32[8]{0} parameter(0)
  ROOT %fusion.9 = f32[8]{0} fusion(%p2), kind=kCustom, calls=%inner
}

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/optimizer/mul"}
  ROOT %select.2 = f32[8]{0} select(%p0, %mul.1, %p0), metadata={op_name="jit(f)/guard/select_n" stack_frame_id=3}
}

ENTRY %main.5 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %copy.1 = f32[8]{0} copy(%a)
  %fusion.8 = f32[8]{0} fusion(%copy.1), kind=kCustom, calls=%outer
  ROOT %select_fusion = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/guard/select_n"}
}
"""
    assert perf.hlo_scopes(text) == {
        # a fusion nested in a nameless fusion still says whose it is
        "p1": ("",),
        "add.7": ("jit(f)/moe/router/add_any",),
        "p2": ("",),
        "fusion.9": ("", "jit(f)/moe/router/add_any"),
        "fusion.8": ("", "jit(f)/moe/router/add_any"),
        "p0": ("",),
        "mul.1": ("jit(f)/optimizer/mul",),
        "select.2": ("jit(f)/guard/select_n",),
        "a": ("a",),
        "copy.1": ("",),
        "select_fusion": (
            "jit(f)/guard/select_n", "jit(f)/optimizer/mul",
            "jit(f)/guard/select_n",
        ),
    }


def test_slowmo_step_is_tracked_under_its_own_label():
    cfg = llama.llama_test()
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2), devices=jax.devices()[:4])
    opt = SlowMomentumOptimizer(optax.sgd(0.1), base_lr=0.1, slowmo_freq=2)
    init_fn, step_fn = ts.make_slowmo_train_step(cfg, mesh, opt)
    c0 = _count("compile.count{program=train_step_slowmo}")
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (2, 4, 32), 0, cfg.vocab_size),
        ts.slowmo_batch_sharding(mesh),
    )
    batch = {"tokens": tokens, "targets": tokens}
    text = step_fn.lower(state, batch).compile().as_text()
    assert "/loss/" in text and "/optimizer/" in text
    for _ in range(2):
        state, _ = step_fn(state, batch)
    assert _count("compile.count{program=train_step_slowmo}") - c0 == 1
