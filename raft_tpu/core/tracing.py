"""Tracing ranges — parity with ``cpp/include/raft/core/nvtx.hpp``.

RAFT provides RAII NVTX ranges (``common::nvtx::range``, ``core/nvtx.hpp:14-57``)
compiled out unless ``RAFT_NVTX`` is on.  The TPU analog is
``jax.profiler.TraceAnnotation`` (shows up in XProf/Perfetto timelines) plus
``jax.named_scope`` so the annotation also lands in HLO names.  Enabled by
default; set ``RAFT_TPU_TRACING=0`` to compile it out to a no-op.

Unified with :mod:`raft_tpu.obs` (ISSUE 9): every range additionally
records a structured span into the process flight recorder
(:func:`raft_tpu.obs.spans.recorder`), auto-parented by the calling
thread's open ranges — so engine/build/serve annotations that used to be
profiler-only are retained in the always-on ring buffer and come out in
stall dumps and Perfetto exports.  ``RAFT_OBS_SPANS=0`` disables just
the recording half; ``RAFT_TPU_TRACING=0`` disables both.

One clock: the span is opened first and the profiler annotation second,
carrying the span's ``span_id`` and ``t_ns`` (its start on the recorder's
clock) as annotation metadata.  A ``jax.profiler`` capture stamps its
events from its own start, so ``start_ns - t_ns`` of any annotation in it
is the one offset that lays the capture over the flight recorder.

Push/pop discipline (satellite of ISSUE 9): :func:`pop_range` is safe on
an empty per-thread stack (returns ``False`` and counts
``raft_tracing_unbalanced_pops_total`` instead of raising or silently
hiding the imbalance) and is exception-safe — the obs span always
finishes and the stack entry always pops, even when the underlying
annotation's ``__exit__`` raises.
"""

from __future__ import annotations

import contextlib
import os
import threading
from functools import wraps

import jax

__all__ = ["range", "annotate", "push_range", "pop_range", "stack_depth"]

_ENABLED = os.environ.get("RAFT_TPU_TRACING", "1") != "0"
_tls = threading.local()


def _stack() -> list:
    # Per-thread like NVTX push/pop: annotations must not cross threads.
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def _recorder():
    from ..obs.spans import recorder

    return recorder()


def _annotation(name: str, span):
    """The profiler annotation for ``span``: its id and start on the
    recorder's clock ride along as metadata (the event name is
    unchanged)."""
    if span is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.TraceAnnotation(name, span_id=span.span_id,
                                        t_ns=span.t_start_ns)


@contextlib.contextmanager
def range(fmt: str, *args, **attrs):
    """RAII-style range (``nvtx::range`` parity). Usage::

        with tracing.range("select_k(batch=%d,k=%d)", batch, k):
            ...

    Records a flight-recorder span (auto-parented to the innermost open
    range/span on this thread, carrying ``attrs``), then emits the
    profiler annotation stamped with that span's id and start, and the
    HLO scope.  Yields the span (``None`` when recording is off).
    """
    if not _ENABLED:
        yield None
        return
    name = (fmt % args) if args else fmt
    with _recorder().span(name, **attrs) as span, \
            _annotation(name, span), jax.named_scope(name):
        yield span


def push_range(fmt: str, *args) -> None:
    """Explicit push (``nvtx::push_range``); pair with :func:`pop_range`."""
    if not _ENABLED:
        return
    name = (fmt % args) if args else fmt
    span = _recorder().start(name)
    cm = _annotation(name, span)
    cm.__enter__()
    _stack().append((cm, span))


def pop_range() -> bool:
    """Pop the innermost pushed range.  Returns ``True`` when a range was
    popped; an unbalanced pop (empty stack) is a counted no-op — see the
    module docstring.  The flight-recorder span finishes even when the
    annotation's ``__exit__`` raises."""
    if not _ENABLED:
        return False
    stack = _stack()
    if not stack:
        from ..obs.metrics import registry

        registry().counter(
            "raft_tracing_unbalanced_pops_total",
            "pop_range() calls with no matching push_range()").inc()
        return False
    cm, span = stack.pop()
    try:
        cm.__exit__(None, None, None)
    finally:
        _recorder().finish(span)
    return True


def stack_depth() -> int:
    """Open pushed ranges on the calling thread (test/debug surface)."""
    return len(_stack())


def annotate(name: str = None):
    """Decorator form: annotate a whole function as a range."""

    def deco(fn):
        label = name or fn.__qualname__

        @wraps(fn)
        def wrapper(*args, **kwargs):
            with range(label):
                return fn(*args, **kwargs)

        return wrapper

    return deco
