"""The program's own trace points, on the JAX profiler's clock.

* :func:`span` — a host span, ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>``.  Ids go in as keyword stats (``rid=``, ``pos=``, ...),
  never into the name, so opening one builds no string.  With no profiler
  running, entering and leaving a span took 0.9 µs with two ids and 0.6 µs
  with none on a TPU v5e host (mean over 1e6 cycles), against ~25 ms a
  decode call, so spans stay on everywhere; a trace taken with
  ``jax.profiler.trace`` shows them on the same clock as the device's
  programs and ops.
* :func:`scope` — a ``jax.named_scope``: while tracing a jitted function
  it prefixes the ``op_name`` metadata of every HLO instruction made
  inside it, which the TPU's op events carry into the trace (the
  ``tf_op`` stat).  It changes metadata only, never what is compiled.
* :func:`stage_scope` — the one scope every planned-collective stage runs
  under (``stage<k>.<axis>.<mode>``), so a trace splits a planned
  collective's device time into its stages' communication and the local
  layout work around them.

The names, their ids and the metrics that read them are listed in PERF.md
("Spans, scopes and counters").
"""
from __future__ import annotations

import jax

__all__ = ["span", "scope", "stage_scope"]


def span(name: str, **ids):
    """Host span ``repro.<name>`` with ``ids`` as its stats."""
    return jax.profiler.TraceAnnotation("repro." + name, **ids)


def scope(name: str):
    """Named scope ``name`` for the HLO ops traced inside it."""
    return jax.named_scope(name)


def stage_scope(k: int, axis: str, mode: str):
    """Scope of stage ``k`` of a planned collective: its communication over
    mesh axis ``axis``, run as ``mode`` (``oneshot``, ``ring`` or
    ``exchange``)."""
    return jax.named_scope(f"stage{k}.{axis}.{mode}")
