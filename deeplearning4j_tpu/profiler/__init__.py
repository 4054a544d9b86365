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
- ``scope``: the device's side of the same trace. A ``jax.named_scope``
  from one vocabulary (``profiler/scopes.py``: ``attn/qkv``,
  ``moe/experts``, ``update/step``, the engine programs' ``admit`` /
  ``decode``), which every layer, the training step and the engine's
  programs put around their parts: compile-time metadata that names
  each device operation's layer in a profile (its ``tf_op``), read back
  by ``benchmark/opscopes.py``.

Taking the XLA/TPU-level trace itself (start, stop, reduce) is the
benchmark's ``benchmark/common.py`` ``SubTrace``.
"""

from deeplearning4j_tpu.profiler.scopes import scope
from deeplearning4j_tpu.profiler.tracer import Tracer, annotate

__all__ = ["Tracer", "annotate", "scope"]
