"""Low-overhead span tracer for the serving pipeline.

This module provides:

* :class:`Tracer` — context-manager spans on named **tracks** (one per
  pipeline phase: ``target_verify``, ``draft_generate``, ``rollback``,
  ``prefill``, ``h2d``/``d2h`` weight/KV streaming, ``kv`` ops,
  ``round``), instant events (replans, admissions, evictions), and
  counter samples.  Timestamps come from ``time.perf_counter`` (CLOCK_
  MONOTONIC); a settable ``virtual_clock`` additionally stamps each
  event with the scheduler's virtual time so trace replays line up with
  request metrics.
* **Honest device timing** — JAX dispatch is asynchronous, so a span
  around a jitted call measures dispatch, not compute.  Inside a span,
  ``sp.fence(arrays)`` calls ``jax.block_until_ready`` before the span
  closes (only when the tracer fences; a no-op otherwise).
* **Spans on the device trace's clock** — with ``annotations`` each span
  enters a ``jax.profiler.TraceAnnotation`` named ``<track>/<name>``, so
  a profiler trace shows what the host was doing in every device idle
  gap (``bench/xplane.py`` names each gap by the innermost one).
* **Chrome trace-event export** — :meth:`Tracer.to_chrome_trace`
  returns the JSON object format (``{"traceEvents": [...]}``) loadable
  in Perfetto / ``chrome://tracing``, with one named thread per track.

Zero cost when disabled: :data:`NULL_TRACER` returns one shared no-op
span object from every call — nothing is allocated per round (asserted
by ``tests/test_obs.py``).  Tracer calls sit strictly *outside* jit
boundaries, so enabling tracing never retraces the fused step.
"""
from __future__ import annotations

import threading
import time

try:  # pragma: no cover - exercised indirectly
    import jax as _jax
    from jax.profiler import TraceAnnotation as _TraceAnnotation
    _HAS_JAX = True
except Exception:  # pragma: no cover - obs must import without jax
    _jax = None
    _TraceAnnotation = None
    _HAS_JAX = False

# Canonical pipeline tracks, in display order (Perfetto sorts by tid).
TRACKS = ("round", "target_verify", "draft_generate", "rollback",
          "prefill", "h2d", "d2h", "kv", "admit", "planner")


class _NullSpan:
    """Shared do-nothing span: the disabled-mode fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def fence(self, arrays):
        return arrays

    def rename(self, name):
        return self

    def set(self, key, value):
        return self


NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every entry point is allocation-free."""
    enabled = False
    virtual_clock = None

    def span(self, track, name, cat=None):
        return NULL_SPAN

    def instant(self, track, name, args=None):
        return None

    def complete(self, track, name, t0, t1, cat=None, args=None):
        return None

    def counter(self, track, name, value):
        return None

    def to_chrome_trace(self):
        return {"traceEvents": []}


NULL_TRACER = NullTracer()


class _Span:
    __slots__ = ("_tr", "track", "name", "cat", "t0", "t1", "args",
                 "_fence", "_annot")

    def __init__(self, tracer, track, name, cat):
        self._tr = tracer
        self.track = track
        self.name = name
        self.cat = cat
        self.t0 = self.t1 = 0.0
        self.args = None
        self._fence = None
        self._annot = None

    def __enter__(self):
        if self._tr.use_annotations:
            self._annot = _TraceAnnotation(f"{self.track}/{self.name}")
            self._annot.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._fence is not None and _HAS_JAX:
            _jax.block_until_ready(self._fence)
        self.t1 = time.perf_counter()
        # record inside the annotation: the tracer's own host time then
        # carries this span's name on the device trace
        self._tr._record(self)
        if self._annot is not None:
            self._annot.__exit__(*exc)
        return False

    def fence(self, arrays):
        """Block on ``arrays`` at span exit (when the tracer fences) so
        the span measures device compute, not async dispatch."""
        if self._tr.fence_spans:
            self._fence = arrays
        return arrays

    def rename(self, name):
        self.name = name
        return self

    def set(self, key, value):
        """Attach one key to the span's Chrome-trace ``args``."""
        if self.args is None:
            self.args = {}
        self.args[key] = value
        return self


class Tracer:
    """Recording tracer.  See the module docstring for the API."""
    enabled = True

    def __init__(self, fence: bool = True, annotations: bool = False,
                 virtual_clock=None):
        self.fence_spans = fence
        self.use_annotations = annotations and _TraceAnnotation is not None
        self.virtual_clock = virtual_clock   # callable -> scheduler seconds
        self.t0 = time.perf_counter()
        self.events: list[dict] = []         # chrome trace events (us)
        self._tids: dict[str, int] = {}
        # Guards track creation only: event appends are GIL-atomic, and
        # readers (to_chrome_trace) take one atomic list() copy — the
        # engine's worker thread can keep recording while the asyncio
        # side exports mid-round.
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def _tid(self, track: str) -> int:
        tid = self._tids.get(track)          # fast path: known track
        if tid is None:
            with self._lock:
                tid = self._tids.get(track)
                if tid is None:
                    try:
                        tid = TRACKS.index(track)
                    except ValueError:
                        tid = len(TRACKS) + len(self._tids)
                    self._tids[track] = tid
                    self.events.append(
                        {"ph": "M", "name": "thread_name", "pid": 1,
                         "tid": tid, "args": {"name": track}})
        return tid

    def _us(self, t: float) -> float:
        return (t - self.t0) * 1e6

    def _stamp(self, args: dict | None) -> dict | None:
        if self.virtual_clock is None:
            return args
        args = dict(args) if args else {}
        args["virtual_s"] = float(self.virtual_clock())
        return args

    def _record(self, sp: _Span):
        ev = {"name": sp.name, "ph": "X", "pid": 1, "tid": self._tid(sp.track),
              "ts": self._us(sp.t0),
              "dur": max(0.0, (sp.t1 - sp.t0) * 1e6)}
        if sp.cat:
            ev["cat"] = sp.cat
        args = self._stamp(sp.args)
        if args:
            ev["args"] = args
        self.events.append(ev)

    # ------------------------------------------------------------------
    def span(self, track: str, name: str, cat: str | None = None) -> _Span:
        """Open a complete-event span on ``track`` (context manager)."""
        return _Span(self, track, name, cat)

    def complete(self, track: str, name: str, t0: float, t1: float,
                 cat: str | None = None, args: dict | None = None):
        """Record an already-timed interval (perf_counter seconds), e.g.
        a transfer timed by its caller or a request's phase."""
        ev = {"name": name, "ph": "X", "pid": 1, "tid": self._tid(track),
              "ts": self._us(t0), "dur": max(0.0, (t1 - t0) * 1e6)}
        if cat:
            ev["cat"] = cat
        args = self._stamp(args)
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, track: str, name: str, args: dict | None = None):
        """Thread-scoped instant event (admission, eviction, replan)."""
        ev = {"name": name, "ph": "i", "s": "t", "pid": 1,
              "tid": self._tid(track),
              "ts": self._us(time.perf_counter())}
        args = self._stamp(args)
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, track: str, name: str, value: float):
        """Chrome counter sample (rendered as a stacked area track)."""
        self.events.append({"name": name, "ph": "C", "pid": 1,
                            "tid": self._tid(track),
                            "ts": self._us(time.perf_counter()),
                            "args": {name: float(value)}})

    # ------------------------------------------------------------------
    def to_chrome_trace(self) -> dict:
        """Chrome trace-event JSON (object format), Perfetto-loadable."""
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms",
                "otherData": {"producer": "repro.obs.trace",
                              "clock": "CLOCK_MONOTONIC (perf_counter)"}}
