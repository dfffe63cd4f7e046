"""The train step's named scopes and its place in the compile observatory
(ISSUE 25): ``embed`` / ``attn`` / ``mlp`` / ``head`` / ``optimizer`` /
``guard`` reach the optimized program's ``op_name`` metadata and change
nothing else; the flash kernels carry fixed names; the step is the tracked
program ``train_step``; with a span sink its compile records a scope map,
and without one nothing extra is lowered or compiled."""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import gpt2, llama
from torchdistx_tpu.ops.pallas.flash_attention import flash_attention
from torchdistx_tpu.parallel import train_step as ts
from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh
from torchdistx_tpu.parallel.slowmo import SlowMomentumOptimizer
from torchdistx_tpu.telemetry import perf

SCOPES = ("embed", "attn", "mlp", "head", "optimizer", "guard")
FAMILIES = {
    "gpt2": (gpt2, gpt2.gpt2_test),
    "llama": (llama, llama.llama_test),
}


def _scanned(family):
    """The family's test config with its layers under ``lax.scan`` and
    remat, as at full depth."""
    model, make = FAMILIES[family]
    return model, dataclasses.replace(make(), layer_unroll=1, remat=True)


def _step(family, tx=None):
    model, cfg = _scanned(family)
    mesh = make_mesh(MeshSpec(fsdp=2), devices=jax.devices()[:2])
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, tx or optax.adamw(1e-3), model=model
    )
    return cfg, mesh, init_fn, step_fn


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


def _words(path):
    """The innermost word of each component: ``transpose(jvp(attn))`` ->
    ``attn``."""
    return {
        (re.findall(r"[A-Za-z_]\w*", c) or [""])[-1] for c in path.split("/")
    }


@contextlib.contextmanager
def _sink():
    prev = telemetry.configure(collect=True)
    try:
        yield
    finally:
        telemetry.configure(**prev)


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")


def _bare(hlo_text):
    """Optimized HLO without the tables of source locations at its head
    and without any instruction's metadata."""
    body = hlo_text[hlo_text.index("\n\n%") if "\n\n%" in hlo_text else 0:]
    return _METADATA.sub("", body)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_compiled_step_carries_the_six_scopes(family):
    cfg, mesh, init_fn, step_fn = _step(family)
    state = init_fn(jax.random.PRNGKey(0))
    text = step_fn.lower(state, _batch(cfg, mesh)).compile().as_text()
    seen = set()
    for paths in perf.hlo_scopes(text).values():
        for p in paths:
            seen |= _words(p) & set(SCOPES)
    assert seen == set(SCOPES)
    # the backward pass keeps the names, wrapped by autodiff
    paths = [p for ps in perf.hlo_scopes(text).values() for p in ps]
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

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/optimizer/mul"}
  ROOT %select.2 = f32[8]{0} select(%p0, %mul.1, %p0), metadata={op_name="jit(f)/guard/select_n" stack_frame_id=3}
}

ENTRY %main.5 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %copy.1 = f32[8]{0} copy(%a)
  ROOT %select_fusion = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/guard/select_n"}
}
"""
    assert perf.hlo_scopes(text) == {
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
