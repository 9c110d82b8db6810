"""Host spans of the training runtime, on the profiler's clock.

Every span is a ``jax.profiler`` annotation, so it lands in the same
trace as the device's operations and on the same clock; with no
profiler running a span costs one ``TraceMe`` check.  Nothing is kept
or exported here: the profiler holds the spans and writes them at
``stop_trace``.  A span's parent is the span that encloses it on the
same thread.

Each span carries the first step of its period: ``step_num`` on the
step markers (:func:`step_span`), ``step`` on the others (:func:`span`).
"""

from __future__ import annotations

import jax

__all__ = ["FIT", "PERIOD", "STEP", "STAGE", "DISPATCH", "WAIT", "DRAIN",
           "CHECKPOINT", "span", "step_span"]

FIT = "repro.fit"                  # one Session.fit call
PERIOD = "repro.period"            # one fused period: stage, dispatch, wait
STEP = "repro.step"                # one iteration of the per-step path
STAGE = "repro.stage"              # building and placing a period's rows
DISPATCH = "repro.dispatch"        # enqueueing a period's executables
WAIT = "repro.wait"                # host blocked on the device
DRAIN = "repro.drain"              # the batched device_get of metrics
CHECKPOINT = "repro.checkpoint"    # a checkpoint save


def span(name: str, step: int) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, step=step)


def step_span(name: str, step: int) -> jax.profiler.StepTraceAnnotation:
    """A step marker: the profiler's step analysis keys on ``step_num``."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)
