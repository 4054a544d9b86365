"""Tracing / profiling subsystem.

The reference has NO dedicated tracer (SURVEY.md §5.1) — observability
rides on IterationListener. This module keeps that listener SPI
(``optimize/listeners.py`` ``TracingIterationListener`` feeds a tracer
from a training loop) and adds what a TPU framework actually needs:

- ``Tracer``: host-side span recorder emitting Chrome trace-event JSON
  (load into chrome://tracing or Perfetto), thread-aware. Every span is
  also an annotation of the ``jax.profiler`` trace being taken, so the
  program's spans sit on the device planes' clock.
- ``annotate``: the same annotation for call sites that hold no
  ``Tracer``.

Taking the XLA/TPU-level trace itself (start, stop, reduce) is the
benchmark's ``benchmark/common.py`` ``SubTrace``.
"""

from deeplearning4j_tpu.profiler.tracer import Tracer, annotate

__all__ = ["Tracer", "annotate"]
