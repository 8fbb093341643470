"""Host spans and counters on the serving path, totalled per scheduler tick.

`span(name, **meta)` marks one phase of the host's work at a layer
boundary.  It always does two things:

* it enters ``jax.profiler.TraceAnnotation("rcllm.<name>", **meta)``,
  so whenever a profiler trace is recording, the phase sits on the
  profiler's clock beside the device operations it issued (``rid=``
  metadata ties one request's spans together);
* it adds its *self* time (duration minus the spans nested in it) on
  ``time.perf_counter`` to the tick that is open on this thread.

`count(name, n)` adds bytes or events to the open tick, and one
``jax.monitoring`` listener adds the seconds JAX spends tracing,
lowering and compiling to the open tick, keyed by the function JAX
names.  A trace nested in another (a jitted helper traced inside a
jitted step) counts once, as part of the outer one; a persistent-cache
load happens inside its compile event and is marked in the key.

A `Tick` is the accumulator: the chunked scheduler opens one per tick
(`batching.WorkerState`), and `batching.TickRecord` carries its
totals.  Off a tick, spans still reach the profiler and counters are
dropped.  There is no switch: a span costs about a microsecond, and the
profiler is what turns the trace on.
"""
from __future__ import annotations

import threading
import time
from collections import defaultdict

import jax.monitoring
from jax.profiler import TraceAnnotation

PREFIX = "rcllm."

# seconds JAX spends turning a function into an executable, per function
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = _COMPILE_EVENTS[-1]
# fired inside the backend compile event when the persistent cache hits
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_TAG = " [cache]"

_local = threading.local()


class Tick:
    """``with Tick() as t: ...`` — the accumulator of one tick, open on
    this thread inside a ``sched.tick`` span: self seconds by span name,
    counters, and compile seconds by function; `seconds`, the tick's
    wall time, is set on exit."""

    __slots__ = ("phase_s", "counts", "compiles", "seconds", "_prev", "_span")

    def __init__(self):
        self.phase_s = defaultdict(float)
        self.counts = defaultdict(int)
        self.compiles = []  # (start, end, function), outermost only
        self.seconds = 0.0

    def __enter__(self) -> "Tick":
        self._prev = getattr(_local, "tick", None)
        _local.tick = self
        self._span = span("sched.tick").__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        self.seconds = time.perf_counter() - self._span._t0
        _local.tick = self._prev
        return False

    def compile_s(self) -> dict:
        out = defaultdict(float)
        for start, end, key in self.compiles:
            out[key] += end - start
        return dict(out)


class span:
    """``with span("engine.finalize", rid=3): ...`` — see the module doc."""

    __slots__ = ("name", "_ann", "_tick", "_parent", "_child", "_t0")

    def __init__(self, name: str, **meta):
        self.name = PREFIX + name
        self._ann = TraceAnnotation(self.name, **meta)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._tick = getattr(_local, "tick", None)
        self._parent = getattr(_local, "top", None)
        _local.top = self
        self._child = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        _local.top = self._parent
        if self._parent is not None:
            self._parent._child += dt
        if self._tick is not None:
            self._tick.phase_s[self.name] += dt - self._child
        self._ann.__exit__(*exc)
        return False


def count(name: str, n: int) -> None:
    """Add `n` to counter `name` of the tick open on this thread."""
    t = getattr(_local, "tick", None)
    if t is not None:
        t.counts[name] += int(n)


def nbytes(*arrays) -> int:
    return sum(int(a.nbytes) for a in arrays)


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == _CACHE_LOAD:
        _local.cache_hit = True


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    if event not in _COMPILE_EVENTS:
        return
    key = str(kw.get("fun_name", "?"))
    if event == _BACKEND_COMPILE and getattr(_local, "cache_hit", False):
        _local.cache_hit = False
        key += CACHE_TAG
    t = getattr(_local, "tick", None)
    if t is None:
        return
    # events that ran inside this one were reported first: drop them
    while t.compiles and t.compiles[-1][0] >= start:
        t.compiles.pop()
    t.compiles.append((start, end, key))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_time_span_listener(_on_time_span)
