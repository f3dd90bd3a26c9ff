"""Spans and counters at the program's own layer boundaries.

``span(name, **args)`` opens a profiler host annotation named
``repro.<name>`` (``jax.profiler.TraceAnnotation``), so every program
span lands on the same clock as the device trace and nests, on the
calling thread, around the programs it launches and around JAX's own
lowering and compile annotations.  ``args`` are scalar stats
(``stage=``, ``mb=``, ``peer=``...).  Spans are always on: outside a
profiler session (``jax.profiler.trace``) an annotation records nothing
and costs about a microsecond.

Names are ``repro.<layer>.<call>``:

* ``exec.run_fwd``, ``exec.run_bwd``, ``exec.accumulate``,
  ``exec.adopt_step`` — executor calls (``repro.runtime``);
* ``exec.bwd_saved`` — inside a last stage's ``exec.run_bwd``, the
  backward that consumes its forward's residuals instead of running the
  forward again (``repro.runtime.numeric``);
* ``wire.fwd``, ``wire.bwd`` — the wire codec step
  (``repro.runtime.base.wire_fwd_codec``/``wire_bwd_codec``);
* ``hop.fwd``, ``hop.bwd`` — one trainer hop's numeric work
  (``repro.core.trainer``), args ``mb``, ``stage``, ``peer``;
* ``swarm.barrier`` — the All-Reduce barrier's math and install
  (``repro.core.swarm``), arg ``step``.

Counters are one process-wide store: ``count(key, n)``, ``counters()``
and ``reset()``.  The runtime's retrace counter
(``repro.runtime.numeric.record_trace``) keeps its counts here, and so
does the last stage's backward: ``("exec.bwd_saved", stage)`` where it
consumed its forward's residuals, ``("exec.bwd_recomputed", stage)``
where it ran the forward again.

This module imports nothing of ``repro``, so every layer can use it.
"""
from __future__ import annotations

import threading
from typing import Hashable

import jax

PREFIX = "repro."

_COUNTS: dict[Hashable, int] = {}
_LOCK = threading.Lock()


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Context manager: the host span ``repro.<name>`` with ``args`` as
    its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def count(key: Hashable, n: int = 1) -> None:
    """Add ``n`` to the counter ``key``."""
    with _LOCK:
        _COUNTS[key] = _COUNTS.get(key, 0) + n


def counters() -> dict[Hashable, int]:
    """A copy of every counter."""
    with _LOCK:
        return dict(_COUNTS)


def reset() -> None:
    """Clear every counter."""
    with _LOCK:
        _COUNTS.clear()
