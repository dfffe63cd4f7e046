"""Elastic training loop: periodic checkpointing + automatic resume.

The reference leaves the training loop to the user and checkpoints only the
SlowMo optimizer state (slowmo_optimizer.py:156-189).  On a preemptible TPU
fleet the loop itself is part of the framework's job: run ``n_steps``,
checkpoint every ``checkpoint_every`` steps, and — after a preemption or a
re-shard — resume from the latest checkpoint, *including onto a different
mesh*: restore targets are abstract arrays carrying the new mesh's
shardings, so orbax reads each shard straight to its new owning device
(no full-tensor host round-trip; see utils/checkpoint.py).

Resilience (see :mod:`torchdistx_tpu.resilience` and docs/resilience.md):

* **Preemption** — SIGTERM/SIGINT set a flag (handlers installed on
  entry); every step boundary agrees on it across hosts
  (:func:`~torchdistx_tpu.parallel.distributed.any_flag`), saves a final
  checkpoint at the last completed step, flushes telemetry counters to
  the trace, and returns — the next invocation resumes exactly there.
* **Retries** — checkpoint IO and the data iterator run under a
  :class:`~torchdistx_tpu.resilience.retry.RetryPolicy` (``ckpt.retries``
  / ``data.retries`` counters).
* **Non-finite guard** — steps built by :func:`make_train_step` report
  ``metrics["nonfinite"]``; the loop counts skips (``train.skipped_steps``)
  and raises :class:`~torchdistx_tpu.resilience.guard.NonFiniteError`
  after ``max_consecutive_nonfinite`` in a row.  The flag is read with a
  small lag so the host never stalls dispatch waiting on the device.
* **Fault injection** — the ``data.next`` and ``step.exec`` sites consult
  :mod:`~torchdistx_tpu.resilience.faults` (``TDX_FAULT``), so CI can
  prove every path above deterministically.

Telemetry: every step runs under a ``train.step`` span (with
``TDX_TELEMETRY_JAX=1`` that is a ``StepTraceAnnotation``, so the XLA
profiler's step view works out of the box), and the loop derives
``steps_per_s`` / ``tokens_per_s`` / ``mfu`` throughput, publishing them as
gauges AND merging them into the metrics dict handed to ``on_metrics``.
Throughput is wall time between successive ``step_fn`` returns: dispatch is
async, so the first measured steps read fast until device backpressure
aligns dispatch with execution — steady-state values are the meaningful
ones (the first step, which carries compilation, is skipped entirely).
"""

from __future__ import annotations

import math
import time
from collections import deque
from typing import Any, Callable, Iterable, Optional

from .. import telemetry as _telemetry
from ..resilience import faults as _faults
from ..resilience import guard as _guard
from ..resilience import preemption as _preemption
from ..resilience.retry import RetryPolicy

__all__ = ["fit"]

_T_STEPS = _telemetry.counter("train.steps")
_T_STEPS_S = _telemetry.gauge("train.steps_per_s")
_T_TOKENS_S = _telemetry.gauge("train.tokens_per_s")
_T_MFU = _telemetry.gauge("train.mfu")
_T_DATA_RETRIES = _telemetry.counter("data.retries")
_T_PREEMPTIONS = _telemetry.counter("train.preemptions")
# A step may carry groups of device scalars out beside its loss
# (``metrics[group][name]``: a ``LOSS_HAS_AUX`` family's aux, see
# make_train_step).  The step names them; each is observed into the
# histogram ``<group>.<name>``, read with the guard flag's lag.
_AUX_BOUNDS = tuple(2.0 ** (i / 4) for i in range(97))  # 1 .. 16.8M

# Steps of lag before the host reads a step's `nonfinite` flag: reading a
# device scalar blocks until that step finishes, so checking the freshest
# flag every step would serialize dispatch with execution.  Two steps of
# lag keeps the async-dispatch pipeline full while bounding how late an
# escalation fires.
_NONFINITE_LAG = 2


def _observe_aux(groups) -> None:
    for group, scalars in groups.items():
        for name, value in scalars.items():
            _telemetry.histogram(f"{group}.{name}", _AUX_BOUNDS).observe(
                float(value)
            )


def _batch_tokens(batch) -> Optional[int]:
    """Token count of one batch: the ``tokens`` leaf's element count (the
    ``{"tokens", "targets"}`` convention of make_train_step)."""
    if not isinstance(batch, dict):
        return None
    shape = getattr(batch.get("tokens"), "shape", None)
    if not shape:
        return None
    return int(math.prod(shape))


def fit(
    init_fn: Callable,
    step_fn: Callable,
    batches: Iterable[Any],
    *,
    key,
    n_steps: int,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 100,
    checkpoint_sync: bool = False,
    on_metrics: Optional[Callable[[int, Any], None]] = None,
    tokens_per_batch: Optional[int] = None,
    flops_per_step: Optional[float] = None,
    peak_flops: Optional[float] = None,
    retry: Optional[RetryPolicy] = RetryPolicy(),
    handle_preemption: bool = True,
    max_consecutive_nonfinite: int = 8,
    exit_sync_every: int = 1,
):
    """Run up to ``n_steps`` optimizer steps, resuming from checkpoints.

    ``init_fn(key) -> state`` and ``step_fn(state, batch) -> (state,
    metrics)`` are the pair built by :func:`make_train_step` (any functions
    with those signatures work).  ``batches`` yields one batch per step;
    steps already completed by a restored checkpoint are skipped by
    *advancing* the iterator, so a deterministic data stream stays aligned
    with the optimizer step count after resume.

    Resilience knobs (module docstring has the semantics):

    * ``retry`` — policy for checkpoint IO and batch pulls (None
      disables; the default allows 3 attempts with ~0.1 s backoff).
    * ``handle_preemption`` — install SIGTERM/SIGINT handlers and drain
      gracefully at the next step boundary (checkpoint, flush, return).
      The run is resumable whether it stopped by preemption, crash, or
      completion; callers distinguish via ``state.step`` / the
      checkpoint directory.
    * ``checkpoint_sync`` — wait for each periodic save to commit before
      continuing (defaults to overlapping saves with subsequent steps;
      synchronous saves bound the replay window to exactly
      ``checkpoint_every`` steps even under a hard kill).
    * ``max_consecutive_nonfinite`` — escalation threshold for the
      non-finite guard (``<= 0`` counts skips but never raises).
    * ``exit_sync_every`` — how often (in steps) the cross-host
      exit-flag collective runs.  The default (1, every boundary) is
      always safe; raising it amortizes the per-step host allgather on
      multihost runs with fast steps, at the price of acting on a
      preemption up to that many steps late.  With a value > 1, data
      exhaustion and pull failures still trigger the collective
      immediately, which stays symmetric across hosts as long as
      per-host streams yield the same number of batches (the invariant
      SPMD data pipelines already require — a host with a missing batch
      would hang the jitted step's collectives anyway).

    Throughput telemetry (see module docstring): ``steps_per_s`` is always
    derived; ``tokens_per_s`` additionally needs the batch token count
    (``tokens_per_batch``, or auto-detected from a ``{"tokens": ...}``
    batch dict); ``mfu`` additionally needs ``flops_per_step`` (model
    FLOPs per optimizer step) and ``peak_flops`` (the chip's peak, in
    FLOP/s: ``benchmarks/peaks.json``, by ``device_kind``).  When ``metrics``
    is a dict, the derived values are merged in before ``on_metrics`` sees it.

    Returns ``(state, last_metrics)``.
    """
    import jax

    from .distributed import any_flags

    state = None
    start = 0
    ckptr = None
    if checkpoint_dir is not None:
        from ..utils.checkpoint import Checkpointer

        ckptr = Checkpointer(checkpoint_dir, retry=retry)
        # Abstract restore target: init_fn is jitted with out_shardings, so
        # eval_shape leaves already carry the mesh shardings — no init
        # compute, and never two full states in HBM during restore.
        abstract = jax.eval_shape(init_fn, key)
        step, restored = ckptr.restore_latest(
            target=abstract,
            shardings=jax.tree.map(lambda l: l.sharding, abstract),
        )
        if step is not None:
            state, start = restored, step
    if state is None:
        state = init_fn(key)

    metrics = None
    if start >= n_steps:
        return state, metrics

    handlers_preexisting = True
    if handle_preemption:
        handlers_preexisting = _preemption.installed()
        _preemption.install()

    it = iter(batches)

    def _pull(step):
        """Next batch for ``step``, through fault site + retry policy."""
        first_error = []

        def _next():
            _faults.fire("data.next", step)
            try:
                return next(it)
            except StopIteration:
                if first_error:
                    # A retryable failure already came out of this pull:
                    # a generator-based iterator is CLOSED by it, so this
                    # StopIteration is bogus — surfacing it would make a
                    # real IO error look like clean data exhaustion and
                    # silently truncate the run.  Re-raise the real
                    # error (the retry loop then fails loudly).
                    raise first_error[0]
                raise
            except Exception as e:
                if not first_error:
                    first_error.append(e)
                raise

        if retry is None:
            return _next()
        return retry.call(
            _next, counter=_T_DATA_RETRIES, site=f"data.next[{step}]"
        )

    tracker = _guard.SkipTracker(max_consecutive_nonfinite)
    pending_flags: deque = deque()  # (step, device nonfinite scalar)
    pending_aux: deque = deque()  # (step, the step's groups of scalars)
    completed = start  # last step whose state we hold
    saved_at = start  # last step with a dispatched checkpoint
    preempted = False
    pull_error: Optional[BaseException] = None
    t_prev = None
    step_no = 0  # last data-stream position consumed (1-based steps);
    # starts at 0 even on resume — batches 1..start are pulled and
    # discarded so the deterministic stream realigns with the step count

    try:
        # Fast-forward the data stream to the resume point.  No step runs
        # here and every host resumed from the same checkpoint (same
        # `start`), so the replay length is identical everywhere — no
        # per-batch collective needed (a 50k-step resume must not pay 50k
        # allgathers just to realign the stream).
        while step_no < start and step_no < n_steps:
            try:
                _pull(step_no + 1)
            except StopIteration:
                raise ValueError(
                    f"data stream exhausted at batch {step_no + 1} while "
                    f"replaying to the resume point (checkpoint step "
                    f"{start}): the stream is shorter than the run it is "
                    "supposed to realign with"
                ) from None
            step_no += 1

        while step_no < n_steps:
            pulling = step_no + 1
            batch = None
            exhausted = False
            pull_error = None
            try:
                batch = _pull(pulling)
            except StopIteration:
                exhausted = True
            except Exception as e:
                # Held, not raised: the error must travel through the
                # exit collective first, or this host would abandon the
                # allgather while its peers wait in it (deadlock).  It
                # re-raises below, after the tail checkpoint is saved.
                pull_error = e
            # Step boundary: ONE small collective agrees on every exit
            # cause across hosts — the scheduler signals hosts at
            # different instants and a data source may fail on one host
            # only, but every host must stop at (and checkpoint) the
            # SAME step, and a host that stopped calling the collective
            # while others still wait in it would deadlock the job.
            # Local stop conditions always sync (symmetric across hosts
            # for same-length streams — see exit_sync_every docs);
            # pure preemption polling runs every exit_sync_every steps.
            must_sync = exhausted or pull_error is not None
            if must_sync or pulling % max(1, exit_sync_every) == 0:
                preempted_any, exhausted_any, failed_any = any_flags(
                    (
                        handle_preemption and _preemption.requested(),
                        exhausted,
                        pull_error is not None,
                    )
                )
                if preempted_any:
                    preempted = True
                    break
                if failed_any or exhausted_any:
                    break
            step_no = pulling
            done = step_no
            kind = _faults.fire("step.exec", done)
            if kind == "nan" and isinstance(batch, dict):
                # Cooperative poison: make_train_step turns this
                # reserved key into a NaN loss inside jit, so the
                # injected fault exercises the REAL guard path.
                batch = {**batch, "_tdx_nan": True}
            with _telemetry.span("train.step", step=done):
                state, metrics = step_fn(state, batch)
            completed = done
            _T_STEPS.add()
            now = time.perf_counter()
            if t_prev is not None and now > t_prev:
                steps_per_s = 1.0 / (now - t_prev)
                _T_STEPS_S.set(steps_per_s)
                derived = {"steps_per_s": steps_per_s}
                n_tok = tokens_per_batch or _batch_tokens(batch)
                if n_tok:
                    tokens_per_s = n_tok * steps_per_s
                    _T_TOKENS_S.set(tokens_per_s)
                    derived["tokens_per_s"] = tokens_per_s
                if flops_per_step and peak_flops:
                    mfu = flops_per_step * steps_per_s / peak_flops
                    _T_MFU.set(mfu)
                    derived["mfu"] = mfu
                if isinstance(metrics, dict):
                    metrics = {**metrics, **derived}
            t_prev = now
            if isinstance(metrics, dict) and "nonfinite" in metrics:
                pending_flags.append((done, metrics["nonfinite"]))
                while (
                    pending_flags
                    and done - pending_flags[0][0] >= _NONFINITE_LAG
                ):
                    s, flag = pending_flags.popleft()
                    tracker.observe(bool(flag), s)
            groups = {
                k: v for k, v in metrics.items() if isinstance(v, dict)
            } if isinstance(metrics, dict) else {}
            if groups:
                pending_aux.append((done, groups))
            while pending_aux and done - pending_aux[0][0] >= _NONFINITE_LAG:
                _observe_aux(pending_aux.popleft()[1])
            if on_metrics is not None:
                on_metrics(done, metrics)
            if ckptr is not None and (
                done % checkpoint_every == 0 or done == n_steps
            ):
                # Saves overlap with subsequent steps unless
                # checkpoint_sync; the finally below finalizes whichever
                # save is still in flight — including when a later step
                # raises, so every dispatched checkpoint stays durable
                # for the post-crash resume.
                ckptr.save(done, state, wait=checkpoint_sync)
                saved_at = done

        # Drain the lagged guard flags so a poisoned tail still counts
        # (and can still escalate) before the loop returns.
        while pending_flags:
            s, flag = pending_flags.popleft()
            tracker.observe(bool(flag), s)
        while pending_aux:
            _observe_aux(pending_aux.popleft()[1])

        # Always persist the final completed step: the loop may exit with
        # work done since the last periodic save — `batches` exhausted
        # before n_steps, or a preemption — and losing that tail would
        # silently rewind the resume point.
        if ckptr is not None and completed > saved_at:
            ckptr.save(completed, state, wait=False)
            saved_at = completed
        if preempted:
            _T_PREEMPTIONS.add()
            with _telemetry.span("train.preempt", step=completed):
                pass  # event span: the preemption is visible in traces
            # The request has been acted on (state saved): clear it so a
            # later fit() in the same process can resume instead of
            # instantly re-preempting.  A platform that is really going
            # down keeps signalling.
            _preemption.clear()
    finally:
        if ckptr is not None:
            ckptr.wait_until_finished()
        if handle_preemption and not handlers_preexisting:
            # Restore whatever handlers the caller had: fit() must not
            # permanently swallow the user's Ctrl-C.
            _preemption.uninstall()
    if pull_error is not None:
        # The failure that stopped the loop, raised only now: progress
        # up to the agreed stop step is already checkpointed, and every
        # host left the collective cleanly first.
        raise pull_error
    if preempted:
        # Flush counters (retries, skips, the preemption itself) to the
        # JSONL trace before the process is torn down.
        _telemetry.emit_counters()
    return state, metrics
