"""Resilience subsystem: retry policy, fault injection, preemption flag,
non-finite guard, and their wiring through Checkpointer and fit().

The pure parts (retry/faults/preemption) run in the torch-only
environment; guard/fit integration tests need JAX and skip without it.
The JAX-integration classes are marked ``slow`` (the tier-1 lane runs
``-m 'not slow'`` under a tight wall-clock budget) and run in full in
CI's fault-injection lane together with tests/test_crash_resume.py.
"""

import os
import signal

import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.resilience import (
    CRASH_EXIT_CODE,
    InjectedFault,
    NonFiniteError,
    RetriesExhausted,
    RetryPolicy,
    SkipTracker,
    faults,
    parse_faults,
    preemption,
)
from torchdistx_tpu.resilience.retry import DEFAULT_RETRYABLE_NAMES


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Every test starts with an empty fault registry and a clear
    preemption flag, and leaves no handlers behind."""
    faults.reset("")
    preemption.clear()
    yield
    faults.reset(None if os.environ.get("TDX_FAULT") else "")
    preemption.clear()
    preemption.uninstall()


# ---------------------------------------------------------------------------
# RetryPolicy


class TestRetryPolicy:
    def test_succeeds_after_transient_failures(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        c = telemetry.counter("test.retries")
        before = c.value
        p = RetryPolicy(max_attempts=5, base_delay_s=0.001)
        assert p.call(flaky, counter=c) == "ok"
        assert len(calls) == 3
        assert c.value - before == 2  # two granted retries

    def test_exhausted_raises_with_cause(self):
        p = RetryPolicy(max_attempts=2, base_delay_s=0.001)

        def always():
            raise OSError("persistent")

        with pytest.raises(RetriesExhausted) as ei:
            p.call(always)
        assert isinstance(ei.value.__cause__, OSError)

    def test_non_retryable_propagates_immediately(self):
        calls = []

        def fatal():
            calls.append(1)
            raise ValueError("not transient")

        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=5, base_delay_s=0.001).call(fatal)
        assert len(calls) == 1

    def test_retryable_by_name(self):
        class Unavailable(Exception):  # grpc-style transport error
            pass

        p = RetryPolicy(max_attempts=2, base_delay_s=0.001)
        assert p.is_retryable(Unavailable())
        assert not p.is_retryable(KeyError())

    def test_explicit_retryable_attribute_is_authoritative(self):
        """An exception carrying a boolean `retryable` (the serving
        RequestError contract) overrides BOTH the isinstance layer and
        the name layer — the router, checkpoint IO, and data IO all
        classify through this one path."""
        p = RetryPolicy(max_attempts=2, base_delay_s=0.001)

        class TransientThing(Exception):  # not an OSError, unknown name
            retryable = True

        class FatalIO(OSError):  # isinstance says retry; raiser says no
            retryable = False

        assert p.is_retryable(TransientThing())
        assert not p.is_retryable(FatalIO())
        # A non-boolean attribute is ignored — heuristics still apply.
        class WeirdAttr(OSError):
            retryable = "yes"

        assert p.is_retryable(WeirdAttr())

    def test_retryable_attribute_request_error_contract(self):
        """End-to-end with the serving taxonomy: a shed/drain is
        retryable; a serving DeadlineExceeded is NOT, even though its
        NAME collides with grpc's transient DeadlineExceeded status."""
        from torchdistx_tpu.serving import (
            DeadlineExceeded,
            EngineDraining,
            EngineOverloaded,
            RequestCancelled,
        )

        p = RetryPolicy(max_attempts=2, base_delay_s=0.001)
        assert p.is_retryable(EngineOverloaded("shed"))
        assert p.is_retryable(EngineDraining("draining"))
        assert not p.is_retryable(RequestCancelled("client cancel"))
        assert not p.is_retryable(DeadlineExceeded("too late"))
        assert "DeadlineExceeded" in DEFAULT_RETRYABLE_NAMES  # the trap

    def test_retryable_attribute_drives_call(self):
        """call() grants retries on attribute-classified exceptions and
        stops immediately on retryable=False ones."""
        p = RetryPolicy(max_attempts=3, base_delay_s=0.001)

        class Transient(Exception):
            retryable = True

        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise Transient("hiccup")
            return "ok"

        assert p.call(flaky) == "ok"
        assert len(calls) == 2

        class Fatal(OSError):
            retryable = False

        fatal_calls = []

        def fatal():
            fatal_calls.append(1)
            raise Fatal("corrupt")

        with pytest.raises(Fatal):
            p.call(fatal)
        assert len(fatal_calls) == 1

    def test_delay_backoff_bounds(self):
        p = RetryPolicy(base_delay_s=0.1, max_delay_s=1.0, jitter=0.5)
        for k, cap in [(0, 0.1), (1, 0.2), (2, 0.4), (10, 1.0)]:
            for _ in range(8):
                d = p.delay(k)
                assert cap * 0.5 <= d <= cap

    def test_deadline_bounds_total_time(self):
        p = RetryPolicy(
            max_attempts=100, base_delay_s=10.0, deadline_s=0.01
        )

        def always():
            raise OSError("x")

        # The first retry's sleep would cross the deadline: no 10s nap.
        with pytest.raises(RetriesExhausted):
            p.call(always)

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(jitter=1.5)


# ---------------------------------------------------------------------------
# Fault injection


class TestFaults:
    def test_parse_grammar(self):
        specs = parse_faults("ckpt.save:2:io, step.exec:3:nan")
        assert [(s.site, s.step, s.kind) for s in specs] == [
            ("ckpt.save", 2, "io"),
            ("step.exec", 3, "nan"),
        ]

    @pytest.mark.parametrize(
        "bad",
        [
            "ckpt.save:2",  # missing kind
            "nowhere:2:io",  # unknown site
            "ckpt.save:2:explode",  # unknown kind
            "ckpt.save:x:io",  # non-int step
            "ckpt.save:0:io",  # steps are 1-based
        ],
    )
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_faults(bad)

    def test_fire_once_then_clean(self):
        faults.reset("data.next:4:io")
        assert faults.fire("data.next", 3) is None  # wrong step
        assert faults.fire("ckpt.save", 4) is None  # wrong site
        with pytest.raises(InjectedFault):
            faults.fire("data.next", 4)
        # Consumed: the retry's second attempt succeeds.
        assert faults.fire("data.next", 4) is None

    def test_nan_kind_is_returned_not_raised(self):
        faults.reset("step.exec:1:nan")
        assert faults.fire("step.exec", 1) == "nan"

    def test_fired_counter(self):
        c = telemetry.counter("faults.fired")
        before = c.value
        faults.reset("data.next:1:nan")
        faults.fire("data.next", 1)
        assert c.value - before == 1

    def test_crash_exit_code_reserved(self):
        # The subprocess e2e (test_crash_resume.py) asserts this code.
        assert CRASH_EXIT_CODE == 13


# ---------------------------------------------------------------------------
# Preemption flag


class TestPreemption:
    def test_request_and_clear(self):
        assert not preemption.requested()
        preemption.request()
        assert preemption.requested()
        preemption.clear()
        assert not preemption.requested()

    def test_real_sigterm_sets_flag(self):
        assert preemption.install()
        assert preemption.installed()
        c = telemetry.counter("preempt.signals")
        before = c.value
        os.kill(os.getpid(), signal.SIGTERM)
        # CPython delivers the handler at a bytecode boundary right after
        # the kill returns in the main thread.
        for _ in range(1000):
            if preemption.requested():
                break
        assert preemption.requested()
        assert c.value - before == 1

    def test_second_signal_escalates_to_previous_handler(self):
        hits = []
        prev = signal.signal(signal.SIGTERM, lambda s, f: hits.append(s))
        try:
            assert preemption.install()
            os.kill(os.getpid(), signal.SIGTERM)
            for _ in range(1000):
                if preemption.requested():
                    break
            assert hits == []  # first signal: flag only
            os.kill(os.getpid(), signal.SIGTERM)
            for _ in range(1000):
                if hits:
                    break
            assert hits == [signal.SIGTERM]  # second: chained
        finally:
            preemption.uninstall()
            signal.signal(signal.SIGTERM, prev)

    def test_uninstall_restores(self):
        prev = signal.getsignal(signal.SIGTERM)
        preemption.install()
        preemption.uninstall()
        assert signal.getsignal(signal.SIGTERM) is prev


# ---------------------------------------------------------------------------
# Non-finite guard (host side)


class TestSkipTracker:
    def test_escalates_after_consecutive(self):
        t = SkipTracker(max_consecutive=3)
        t.observe(True, 1)
        t.observe(True, 2)
        t.observe(False, 3)  # finite step resets the streak
        t.observe(True, 4)
        t.observe(True, 5)
        with pytest.raises(NonFiniteError) as ei:
            t.observe(True, 6)
        assert ei.value.step == 6
        assert ei.value.consecutive == 3
        assert t.total == 5

    def test_disabled_escalation_still_counts(self):
        c = telemetry.counter("train.skipped_steps")
        before = c.value
        t = SkipTracker(max_consecutive=0)
        for s in range(1, 20):
            t.observe(True, s)
        assert c.value - before == 19


# ---------------------------------------------------------------------------
# JAX integration: guard inside make_train_step, resilience through fit()


@pytest.fixture(scope="module")
def train_rig():
    jax = pytest.importorskip("jax")
    optax = pytest.importorskip("optax")
    pytest.importorskip("orbax.checkpoint")
    from torchdistx_tpu.models import llama
    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = llama.llama_test()
    mesh = make_mesh(MeshSpec(dp=8))
    init_fn, step_fn = ts.make_train_step(cfg, mesh, optax.sgd(0.1))
    bs = ts.batch_sharding(mesh)

    def batches(n=None):
        key = jax.random.PRNGKey(42)
        i = 0
        while n is None or i < n:
            key, sub = jax.random.split(key)
            t = jax.device_put(
                jax.random.randint(sub, (8, 16), 0, cfg.vocab_size), bs
            )
            yield {"tokens": t, "targets": t}
            i += 1

    return cfg, mesh, init_fn, step_fn, batches


@pytest.mark.slow
class TestNonFiniteGuard:
    def test_skip_step_returns_prior_state_bit_identical(self, train_rig):
        import jax
        import numpy as np

        _, _, init_fn, step_fn, batches = train_rig
        batch = next(batches(1))
        state, m1 = step_fn(init_fn(jax.random.PRNGKey(0)), batch)
        assert not bool(m1["nonfinite"])
        assert int(m1["step"]) == 1
        snap = jax.tree.map(np.asarray, state)
        state2, m2 = step_fn(state, {**batch, "_tdx_nan": True})
        assert bool(m2["nonfinite"])
        assert not np.isfinite(float(m2["loss"]))
        for a, b in zip(
            jax.tree.leaves(snap),
            jax.tree.leaves(jax.tree.map(np.asarray, state2)),
        ):
            np.testing.assert_array_equal(a, b)
        # Training continues cleanly after the skip.
        state3, m3 = step_fn(state2, batch)
        assert int(m3["step"]) == 2
        assert not bool(m3["nonfinite"])

    def test_guard_composes_with_fsdp_tp_sharding(self, train_rig):
        """The finiteness check must all-reduce across sharded axes and
        the skip-select must respect per-leaf shardings (wq/wo carry
        transposed fsdp×tp specs) — the composition the dp-only tests
        above cannot see."""
        import jax
        import numpy as np
        import optax

        from torchdistx_tpu.parallel import train_step as ts
        from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

        cfg, _, _, _, _ = train_rig
        mesh = make_mesh(MeshSpec(fsdp=2, tp=4))
        init_fn, step_fn = ts.make_train_step(cfg, mesh, optax.adamw(1e-3))
        state = init_fn(jax.random.PRNGKey(0))
        t = jax.device_put(
            jax.random.randint(
                jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
            ),
            ts.batch_sharding(mesh),
        )
        batch = {"tokens": t, "targets": t}
        state, m1 = step_fn(state, batch)
        assert not bool(m1["nonfinite"])
        snap = jax.tree.map(np.asarray, state)
        state, m2 = step_fn(state, {**batch, "_tdx_nan": True})
        assert bool(m2["nonfinite"])
        for a, b in zip(
            jax.tree.leaves(snap),
            jax.tree.leaves(jax.tree.map(np.asarray, state)),
        ):
            np.testing.assert_array_equal(a, b)
        # Output placement survives the select.
        wq = state.params["layers"]["wq"]
        assert wq.sharding.spec == jax.sharding.PartitionSpec(
            None, "fsdp", "tp"
        )

    def test_guard_off_keeps_legacy_metrics(self, train_rig):
        import jax
        import optax

        cfg, mesh, _, _, batches = train_rig
        from torchdistx_tpu.parallel import train_step as ts

        init_u, step_u = ts.make_train_step(
            cfg, mesh, optax.sgd(0.1), nonfinite_guard=False
        )
        _, m = step_u(init_u(jax.random.PRNGKey(0)), next(batches(1)))
        assert "nonfinite" not in m


@pytest.mark.slow
class TestFitResilience:
    def test_ckpt_save_fault_is_retried(self, train_rig, tmp_path):
        import jax

        from torchdistx_tpu.parallel.fit import fit
        from torchdistx_tpu.utils.checkpoint import latest_step

        _, _, init_fn, step_fn, batches = train_rig
        c = telemetry.counter("ckpt.retries")
        before = c.value
        faults.reset("ckpt.save:2:io")
        fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=3, checkpoint_dir=str(tmp_path / "run"),
            checkpoint_every=2,
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.01),
        )
        assert c.value - before >= 1
        assert latest_step(tmp_path / "run") == 3

    def test_ckpt_fault_without_retry_is_fatal(self, train_rig, tmp_path):
        import jax

        from torchdistx_tpu.parallel.fit import fit

        _, _, init_fn, step_fn, batches = train_rig
        faults.reset("ckpt.save:2:io")
        with pytest.raises(InjectedFault):
            fit(
                init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
                n_steps=3, checkpoint_dir=str(tmp_path / "run"),
                checkpoint_every=2, retry=None,
            )

    def test_data_fault_is_retried(self, train_rig):
        import jax

        from torchdistx_tpu.parallel.fit import fit

        _, _, init_fn, step_fn, batches = train_rig
        c = telemetry.counter("data.retries")
        before = c.value
        faults.reset("data.next:2:io")
        state, _ = fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=3,
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.01),
        )
        assert c.value - before >= 1
        assert int(state.step) == 3

    def test_final_step_saved_when_batches_exhaust(
        self, train_rig, tmp_path
    ):
        import jax

        from torchdistx_tpu.parallel.fit import fit
        from torchdistx_tpu.utils.checkpoint import latest_step

        _, _, init_fn, step_fn, batches = train_rig
        # 3 batches, n_steps=10, checkpoint_every=100: without the
        # final-save path the run would leave NO checkpoint at all.
        fit(
            init_fn, step_fn, batches(3), key=jax.random.PRNGKey(0),
            n_steps=10, checkpoint_dir=str(tmp_path / "run"),
            checkpoint_every=100,
        )
        assert latest_step(tmp_path / "run") == 3

    def test_nonfinite_step_skipped_and_counted(self, train_rig):
        import jax

        from torchdistx_tpu.parallel.fit import fit

        _, _, init_fn, step_fn, batches = train_rig
        c = telemetry.counter("train.skipped_steps")
        before = c.value
        faults.reset("step.exec:2:nan")
        state, _ = fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=4,
        )
        assert c.value - before == 1
        # 4 batches consumed, 3 optimizer steps applied (one skipped).
        assert int(state.step) == 3

    def test_nonfinite_escalation_raises(self, train_rig):
        import jax

        from torchdistx_tpu.parallel.fit import fit

        _, _, init_fn, step_fn, batches = train_rig
        faults.reset("step.exec:1:nan,step.exec:2:nan,step.exec:3:nan")
        with pytest.raises(NonFiniteError):
            fit(
                init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
                n_steps=6, max_consecutive_nonfinite=3,
            )

    def test_preemption_saves_current_step_and_resumes(
        self, train_rig, tmp_path
    ):
        import jax
        import numpy as np

        from torchdistx_tpu.parallel.fit import fit
        from torchdistx_tpu.utils.checkpoint import latest_step

        _, _, init_fn, step_fn, batches = train_rig
        c = telemetry.counter("train.preemptions")
        before = c.value

        def preempt_at_2(step, metrics):
            if step == 2:
                preemption.request()

        fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=10, checkpoint_dir=str(tmp_path / "run"),
            checkpoint_every=100, on_metrics=preempt_at_2,
        )
        # Stopped at the boundary after step 2 and saved THAT step, far
        # from any checkpoint_every multiple.
        assert latest_step(tmp_path / "run") == 2
        assert c.value - before == 1
        # fit() acted on the request and cleared it: the next fit() in
        # this process resumes instead of instantly re-preempting.
        assert not preemption.requested()

        resumed, _ = fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=5, checkpoint_dir=str(tmp_path / "run"),
            checkpoint_every=100,
        )
        ref, _ = fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=5, handle_preemption=False,
        )
        assert int(resumed.step) == 5
        for a, b in zip(
            jax.tree.leaves(ref.params), jax.tree.leaves(resumed.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
            )

    def test_preemption_before_any_step_is_resumable_noop(
        self, train_rig, tmp_path
    ):
        import jax

        from torchdistx_tpu.parallel.fit import fit
        from torchdistx_tpu.utils.checkpoint import latest_step

        _, _, init_fn, step_fn, batches = train_rig
        preemption.request()
        state, metrics = fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=5, checkpoint_dir=str(tmp_path / "run"),
        )
        assert metrics is None  # no step ran
        assert latest_step(tmp_path / "run") is None  # nothing to save

    def test_fit_restores_signal_handlers_on_exit(self, train_rig):
        import jax

        from torchdistx_tpu.parallel.fit import fit

        _, _, init_fn, step_fn, batches = train_rig
        prev_term = signal.getsignal(signal.SIGTERM)
        prev_int = signal.getsignal(signal.SIGINT)
        fit(
            init_fn, step_fn, batches(), key=jax.random.PRNGKey(0),
            n_steps=1,
        )
        # fit() must not permanently swallow the user's Ctrl-C.
        assert signal.getsignal(signal.SIGTERM) is prev_term
        assert signal.getsignal(signal.SIGINT) is prev_int

    def test_transient_error_from_generator_fails_loudly(self, train_rig):
        """A transient error raised INSIDE a generator closes it; the
        retry's follow-up next() then reports StopIteration.  That must
        surface as the original loud failure, never as silent clean
        'data exhausted' truncation of the run."""
        import jax

        from torchdistx_tpu.parallel.fit import fit

        _, _, init_fn, step_fn, batches = train_rig

        def flaky_batches():
            inner = batches()
            yield next(inner)
            raise OSError("transient read error inside the generator")

        with pytest.raises(RetriesExhausted) as ei:
            fit(
                init_fn, step_fn, flaky_batches(),
                key=jax.random.PRNGKey(0), n_steps=5,
                retry=RetryPolicy(max_attempts=3, base_delay_s=0.01),
            )
        assert isinstance(ei.value.__cause__, OSError)


class TestPureReads:
    def test_latest_step_does_not_create_directory(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from torchdistx_tpu.utils.checkpoint import latest_step

        missing = tmp_path / "never-checkpointed"
        assert latest_step(missing) is None
        assert not missing.exists()


class TestAnyFlag:
    def test_single_process_is_local(self):
        pytest.importorskip("jax")
        from torchdistx_tpu.parallel.distributed import any_flag

        assert any_flag(True) is True
        assert any_flag(False) is False


class TestCompileCacheErrorCounter:
    def test_setup_failure_is_counted(self, monkeypatch):
        jax = pytest.importorskip("jax")
        from torchdistx_tpu.utils import compilation_cache as cc

        c = telemetry.counter("compile_cache.errors")
        before = c.value
        monkeypatch.setattr(cc, "_done", False)
        monkeypatch.delenv("TDX_NO_COMPILATION_CACHE", raising=False)
        # Force the accelerator path then fail the mkdir: the swallowed
        # error must surface in the counter.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            cc.os, "makedirs",
            lambda *a, **k: (_ for _ in ()).throw(OSError("read-only")),
        )
        try:
            cc.ensure_compilation_cache()
        finally:
            cc._done = True  # leave the module in its settled state
        assert c.value - before == 1


class TestCompileCacheDirectory:
    """Where the two on-disk tiers live: placed from outside when
    ``JAX_COMPILATION_CACHE_DIR`` is set, else one fixed path in the
    checkout — never the home directory."""

    @pytest.fixture
    def on_accelerator(self, monkeypatch):
        """Fake the accelerator path and record (not apply) every
        ``jax.config.update``, so no test leaves a cache directory
        configured for the CPU suite."""
        jax = pytest.importorskip("jax")
        from torchdistx_tpu.utils import compilation_cache as cc

        updates = []
        monkeypatch.setattr(cc, "_done", False)
        monkeypatch.delenv("TDX_NO_COMPILATION_CACHE", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.append((k, v))
        )
        made = []
        monkeypatch.setattr(
            cc.os, "makedirs", lambda d, **k: made.append(d)
        )
        yield cc, updates, made
        cc._done = True  # settled state, as the suite found it

    def test_unset_uses_the_fixed_in_checkout_path(self, on_accelerator):
        cc, updates, made = on_accelerator
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        cc.ensure_compilation_cache()
        assert updates == [
            ("jax_compilation_cache_dir", cc.DEFAULT_CACHE_DIR)
        ]
        assert made == [cc.DEFAULT_CACHE_DIR]
        assert cc.cache_dir() == cc.DEFAULT_CACHE_DIR

    def test_placed_from_outside_configures_nothing(
        self, on_accelerator, monkeypatch, tmp_path
    ):
        """With JAX_COMPILATION_CACHE_DIR set, JAX holds the directory
        (it reads the variable itself at import): the code sets none, and
        the executable tier follows to the same place."""
        import stat

        import jax

        import torchdistx_tpu.materialize as M

        cc, updates, made = on_accelerator
        placed = str(tmp_path / "placed")
        monkeypatch.setattr(
            type(jax.config), "jax_compilation_cache_dir",
            property(lambda self: placed), raising=False,
        )
        cc.ensure_compilation_cache()
        assert updates == [] and made == []
        assert cc.cache_dir() == placed
        monkeypatch.undo()  # real makedirs back for the tier below
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            type(jax.config), "jax_compilation_cache_dir",
            property(lambda self: placed), raising=False,
        )
        d = M._exec_disk_dir()
        assert d == os.path.join(placed, "tdx_exec")
        assert stat.S_IMODE(os.stat(d).st_mode) == 0o700

        # A refused tier (group-writable: never unpickle from it) is
        # counted, not silent.
        c = telemetry.counter("compile_cache.errors")
        before = c.value
        os.chmod(d, 0o770)
        assert M._exec_disk_dir() is None
        assert c.value - before == 1
