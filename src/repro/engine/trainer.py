"""The one training loop every driver/benchmark/example shares.

``Trainer.run`` absorbs the hand-rolled loops that used to live in
``benchmarks/common.py:run_engine``, ``launch/train.py:main`` and the
examples: step the engine over a batch source, evaluate on a cadence, stop
at a quality target, and fan every side concern (coherence control,
checkpointing, metric sinks) out to hooks.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable, List, Optional, Sequence

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.engine.api import Engine, EngineState

Pytree = Any


@dataclasses.dataclass
class StepContext:
    """What hooks see each step. Hooks may replace ``state`` (e.g. the
    coherence controller clamping the staleness bound) and merge extra
    columns into ``row`` when one is being emitted."""
    engine: Engine
    state: EngineState
    step: int                      # 0-based index of the step just taken
    metrics: dict                  # engine metrics (jax scalars)
    row: Optional[dict] = None     # log row being assembled, if any


class Hook:
    """Base class: override any subset. See hooks.py for implementations."""

    def on_start(self, ctx: StepContext) -> None: ...

    def on_step(self, ctx: StepContext) -> None: ...

    def on_log(self, ctx: StepContext) -> None: ...

    def on_eval(self, ctx: StepContext, value: float) -> None: ...

    def on_end(self, ctx: StepContext, result: "TrainResult") -> None: ...


@dataclasses.dataclass
class TrainResult:
    state: EngineState
    history: list                  # emitted log rows
    curve: list                    # [(worker batches processed, eval value)]
    batches_to_target: Optional[int]
    converged: bool
    wall_s: float


@dataclasses.dataclass
class Trainer:
    """Mode-agnostic loop over a uniform :class:`Engine`."""
    engine: Engine
    hooks: Sequence[Hook] = ()

    def run(self, batches, steps: int, *,
            state: Optional[EngineState] = None,
            init_key: Optional[jax.Array] = None,
            eval_fn: Optional[Callable[[Pytree], Any]] = None,
            eval_every: int = 0,
            target: Optional[float] = None,
            higher_better: bool = True,
            log_every: int = 0) -> TrainResult:
        """Run up to ``steps`` engine steps.

        ``batches`` is an iterable of engine batches or a 0-arg callable
        producing the next batch.  ``eval_fn(params) -> scalar`` runs every
        ``eval_every`` steps (jit-compiled); when ``target`` is set the run
        stops early once the metric crosses it (direction per
        ``higher_better``) and reports worker-batches-to-target — the
        paper's primary measurement.  ``log_every`` emits metric rows that
        hooks (sinks) can consume.

        Each iteration is a profiler step span ``train`` (its ``step_num``
        the 0-based step), holding ``trainer.batch`` (the batch source),
        ``trainer.dispatch`` (``engine.step``), ``trainer.hooks`` (the
        ``on_step`` fan-out), ``trainer.log`` (the log row, where the host
        syncs) and ``trainer.eval``. Spans cost a few microseconds and are
        recorded only while a profiler runs.
        """
        engine = self.engine
        if state is None:
            state = engine.init(init_key if init_key is not None
                                else jax.random.PRNGKey(0))
        next_batch = batches if callable(batches) else iter(batches).__next__
        eval_jit = jax.jit(eval_fn) if eval_fn is not None else None

        ctx = StepContext(engine=engine, state=state, step=-1, metrics={})
        for h in self.hooks:
            h.on_start(ctx)

        t0 = time.monotonic()
        history: List[dict] = []
        curve: list = []
        batches_to_target, converged = None, False
        # Realized-delay running mean over EVERY step (kept as a lazy jax
        # scalar so accumulation never forces a device sync; converted only
        # when a log row is emitted). Accumulating on log rows only — the
        # pre-PR 5 behavior — biased the realized-vs-nominal check toward
        # whatever the delay process happened to do on log-interval steps.
        stale_sum, stale_n = 0.0, 0
        for t in range(steps):
            with StepTraceAnnotation("train", step_num=t):
                with TraceAnnotation("trainer.batch"):
                    try:
                        batch = next_batch()
                    except StopIteration:  # finite source exhausted
                        break
                with TraceAnnotation("trainer.dispatch"):
                    state, metrics = engine.step(ctx.state, batch)
                ctx.state, ctx.step, ctx.metrics, ctx.row = (state, t,
                                                             metrics, None)
                if "mean_staleness" in metrics:
                    stale_sum = stale_sum + metrics["mean_staleness"]
                    stale_n += 1
                with TraceAnnotation("trainer.hooks"):
                    for h in self.hooks:
                        h.on_step(ctx)

                if log_every and (t + 1) % log_every == 0:
                    with TraceAnnotation("trainer.log"):
                        history.append(self._log_row(
                            ctx, t, t0, stale_sum, stale_n))

                if (eval_jit is not None and eval_every
                        and (t + 1) % eval_every == 0):
                    with TraceAnnotation("trainer.eval"):
                        value = float(eval_jit(engine.params(ctx.state)))
                        worker_batches = (t + 1) * engine.batches_per_step
                        curve.append((worker_batches, value))
                        for h in self.hooks:
                            h.on_eval(ctx, value)
                    if target is not None:
                        hit = (value >= target if higher_better
                               else value <= target)
                        if hit:
                            batches_to_target, converged = (worker_batches,
                                                            True)
                            break

        result = TrainResult(
            state=ctx.state, history=history, curve=curve,
            batches_to_target=batches_to_target, converged=converged,
            wall_s=time.monotonic() - t0)
        for h in self.hooks:
            h.on_end(ctx, result)
        return result

    def _log_row(self, ctx: StepContext, t: int, t0: float, stale_sum,
                 stale_n: int) -> dict:
        """Assemble step ``t``'s log row (the host syncs on the metrics
        here) and hand it to the ``on_log`` hooks."""
        metrics = ctx.metrics
        ctx.row = {"step": t + 1, "wall_s": round(time.monotonic() - t0, 2)}
        if "loss" in metrics:
            ctx.row["loss"] = float(metrics["loss"])
        if "mean_staleness" in metrics:
            ctx.row["mean_staleness"] = float(metrics["mean_staleness"])
            # Realized mean TOTAL delay (1 + r) over ALL steps so far —
            # sweeps verify a delay spec's effective staleness against its
            # nominal spec.mean_total_delay.
            ctx.row["mean_total_delay"] = round(
                1.0 + float(stale_sum) / stale_n, 4)
        # Compensation diagnostics (repro.compensate): realized sparsity and
        # the effective stepsize factor, beside the realized delay they
        # compensate.
        if "sparsity" in metrics:
            ctx.row["sparsity"] = round(float(metrics["sparsity"]), 4)
        if "lr_scale" in metrics:
            ctx.row["lr_scale"] = round(
                float(jax.numpy.mean(metrics["lr_scale"])), 6)
        if self.engine._max_bound:
            # live dynamic staleness bound (coherence-controller lever)
            ctx.row["bound"] = int(jax.device_get(ctx.state.bound))
        for h in self.hooks:
            h.on_log(ctx)
        return ctx.row
