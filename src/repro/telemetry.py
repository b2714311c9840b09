"""Where a served request's host time goes: named spans and waits, kept
as process-wide totals and written into the profiler's own trace.

* :func:`span` times work a thread does.  The registry keeps, for each
  name, ``count``, ``seconds`` (inclusive) and ``self_seconds``
  (inclusive minus the spans it encloses on the same thread).  While a
  ``jax.profiler`` session runs, a span is also a
  ``jax.profiler.TraceAnnotation``, so it lands on the host lines of the
  same trace as the device operations.  Annotations never nest on a
  thread: entering a span closes its parent's annotation and leaving it
  reopens one, so every annotated interval is the innermost phase and a
  reduction that names a device idle gap by the host event overlapping
  it most names a phase, not what encloses it.
* :func:`wait` times a thread that blocks, or an interval measured
  between two threads from two timestamps.  Waits are counted and never
  annotated: a blocked thread does no work.
* :func:`context` adds metadata to every span the thread opens inside
  it (``req=<id>`` from the front door, ``batch=<id>`` from the
  coalescing leader).  A query remembers its submitter's context
  (:func:`current`), and the leader writes into it the batch that took
  the query, so the request's later spans carry that batch too.
* :func:`install_gc_hook` times every collector pause as ``gc.pause``
  (per generation, and annotated); a pause counts as a child of the span
  the collecting thread is in.
* :func:`stats` is the ``/stats`` ``spans`` block.  No per-event record
  is kept in memory: the per-event record is the profiler trace.

Names come from :data:`NAMES`; an undeclared name raises.  The totals
are process-wide, like ``core.integrity.COUNTERS``: every front end,
leader thread and the collector of one process write to them.
"""

from __future__ import annotations

import contextlib
import gc
import threading
from time import perf_counter_ns
from typing import Dict, Iterator, Optional

from jax.profiler import TraceAnnotation

__all__ = ["NAMES", "Registry", "REGISTRY", "span", "wait", "context",
           "current", "stats", "install_gc_hook"]

#: every span and wait the program records (``/stats`` ``spans.<name>``)
NAMES = (
    "http.read",        # handler start to the POST body in memory
    "http.reply",       # a POST's answer: JSON encode and socket write
    "rank.lookup",      # response-cache key and probe
    "rank.decode",      # json.loads, the trace's arrays and fingerprint
    "rank.admit",       # quarantine check, deadline and admission
    "rank.queue",       # wait: enqueued to taken by a leader
    "rank.wait",        # wait: the handler blocked on its query
    "rank.encode",      # the answer's wire document and response store
    "engine.pass",      # one union engine pass (planner.sweep)
    "engine.score",     # one scorer call: transfer, launch, readback
    "trace.decode_slow",  # a trace document decoded op by op
    "trace.ops_built",  # a column-decoded trace's ops built on first read
    "trace.track",      # one OperationTracker.track: trace and walk a step
    "trace.jit_walked",  # a nested jit call walked in place
    "trace.scan_walked",  # a scan walked as trip count x its body
    "trace.scan_recurrent",  # a scan kept as one recurrent op
    "trace.priced_default",  # a call or kernel priced as one elementwise op
    "gc.pause",         # a collector pause (install_gc_hook)
)
GC_PAUSE = "gc.pause"
_GENERATIONS = 3


class _Thread:
    """One thread's open spans, context metadata and bookkeeping flag."""
    __slots__ = ("stack", "meta", "busy")

    def __init__(self):
        self.stack: list = []
        self.meta: Optional[dict] = None
        #: inside a span's own bookkeeping: a collection starting now
        #: leaves the stack and its annotations alone
        self.busy = False


class _Span:
    __slots__ = ("_reg", "name", "_meta", "_th", "_t0", "_child", "_ann",
                 "seconds")

    def __init__(self, reg: "Registry", name: str, meta: dict):
        self._reg, self.name, self._meta = reg, name, meta
        self._ann = None
        self._child = 0
        #: inclusive seconds, set when the span closes
        self.seconds = 0.0

    def _open(self, th: _Thread) -> None:
        if TraceAnnotation.is_enabled():
            meta = dict(th.meta, **self._meta) if th.meta else self._meta
            self._ann = TraceAnnotation(self.name, **meta)
            self._ann.__enter__()

    def _close(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self) -> "_Span":
        th = self._th = self._reg._thread()
        th.busy = True
        if th.stack:
            th.stack[-1]._close()
        self._t0 = perf_counter_ns()
        th.stack.append(self)
        self._open(th)
        th.busy = False
        return self

    def __exit__(self, *exc) -> bool:
        th = self._th
        th.busy = True
        ns = perf_counter_ns() - self._t0
        self._close()
        th.stack.pop()
        if th.stack:
            parent = th.stack[-1]
            parent._child += ns
            parent._open(th)
        th.busy = False
        self.seconds = ns / 1e9
        self._reg._add(self.name, ns, ns - self._child)
        return False


class _Wait:
    __slots__ = ("_reg", "name", "_t0")

    def __init__(self, reg: "Registry", name: str):
        self._reg, self.name = reg, name

    def __enter__(self) -> "_Wait":
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        ns = perf_counter_ns() - self._t0
        self._reg._add(self.name, ns, ns)
        return False


class Registry:
    """Totals of declared spans and waits, and the collector's pauses."""

    def __init__(self, names=NAMES):
        self.names = tuple(names)
        self._lock = threading.Lock()
        self._totals = {n: [0, 0, 0] for n in self.names}  # count, ns, self
        # written by the collector hook alone, without the lock: one
        # collection runs at a time, and the hook may fire while this
        # thread holds the lock
        self._gc = [[0, 0] for _ in range(_GENERATIONS)]    # count, ns
        self._gc_t0 = 0
        self._gc_ann = None
        self._local = threading.local()

    def _thread(self) -> _Thread:
        try:
            return self._local.th
        except AttributeError:
            self._local.th = _Thread()
            return self._local.th

    def _check(self, name: str) -> None:
        if name not in self._totals:
            raise ValueError(f"undeclared span {name!r}; declared: "
                             f"{', '.join(self.names)}")

    def _add(self, name: str, ns: int, self_ns: int) -> None:
        with self._lock:
            t = self._totals[name]
            t[0] += 1
            t[1] += ns
            t[2] += self_ns

    def span(self, name: str, **meta) -> _Span:
        """Context manager timing work this thread does; its ``seconds``
        holds the inclusive time once it closed."""
        self._check(name)
        return _Span(self, name, meta)

    def wait(self, name: str, since_ns: Optional[int] = None
             ) -> Optional[_Wait]:
        """A context manager timing a thread that blocks; or, given
        ``since_ns`` (a ``time.perf_counter_ns()`` another thread took),
        the interval from it to now, recorded at once (returns None)."""
        self._check(name)
        if since_ns is None:
            return _Wait(self, name)
        ns = perf_counter_ns() - since_ns
        self._add(name, ns, ns)
        return None

    @contextlib.contextmanager
    def context(self, **meta) -> Iterator[dict]:
        """Metadata for every span this thread opens inside; yields the
        dict, which another thread may add keys to."""
        th = self._thread()
        saved = th.meta
        th.meta = dict(saved, **meta) if saved else dict(meta)
        try:
            yield th.meta
        finally:
            th.meta = saved

    def current(self) -> Optional[dict]:
        """This thread's context metadata (None outside :meth:`context`)."""
        return self._thread().meta

    def install_gc_hook(self) -> None:
        """Time every collector pause, once per process (idempotent)."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        # runs on the collecting thread, possibly inside a span's own
        # bookkeeping (``busy``): then the stack is left alone
        th = self._thread()
        top = th.stack[-1] if th.stack and not th.busy else None
        if phase == "start":
            if TraceAnnotation.is_enabled():
                if top is not None:
                    top._close()
                self._gc_ann = TraceAnnotation(
                    GC_PAUSE, generation=info["generation"])
                self._gc_ann.__enter__()
            self._gc_t0 = perf_counter_ns()
            return
        ns = perf_counter_ns() - self._gc_t0
        if self._gc_ann is not None:
            self._gc_ann.__exit__(None, None, None)
            self._gc_ann = None
            if top is not None:
                top._open(th)
        if top is not None:
            top._child += ns
        g = self._gc[info["generation"]]
        g[0] += 1
        g[1] += ns

    def stats(self) -> Dict:
        """``{name: {count, seconds, self_seconds}}`` for every declared
        name, ``gc.pause`` summed over generations, and ``gc.gen<i>``
        ``{count, seconds}`` per generation."""
        with self._lock:
            totals = {n: list(t) for n, t in self._totals.items()}
        gens = [list(g) for g in self._gc]
        out = {n: {"count": c, "seconds": ns / 1e9, "self_seconds": s / 1e9}
               for n, (c, ns, s) in totals.items()}
        if GC_PAUSE in out:
            ns = sum(g[1] for g in gens)
            out[GC_PAUSE] = {"count": sum(g[0] for g in gens),
                             "seconds": ns / 1e9, "self_seconds": ns / 1e9}
        out["gc"] = {f"gen{i}": {"count": c, "seconds": ns / 1e9}
                     for i, (c, ns) in enumerate(gens)}
        return out


#: the process's registry, behind the module-level functions
REGISTRY = Registry()
span = REGISTRY.span
wait = REGISTRY.wait
context = REGISTRY.context
current = REGISTRY.current
stats = REGISTRY.stats
install_gc_hook = REGISTRY.install_gc_hook
