"""Profiler: per-op aggregates + Chrome-trace dump + device (XLA) tracing.

ref: python/mxnet/profiler.py — set_config/set_state/start/stop/dump/dumps
and the instrumentation objects (Task/Frame/Event/Counter/Marker);
src/profiler/profiler.cc — profiler::Profiler emits Chrome-trace JSON with
one event per engine-dispatched op plus aggregate per-op tables.

TPU-native mapping: host-side spans wrap the ``nd.invoke`` dispatch and the
fused TrainStep (the two places work is scheduled), written out in Chrome
``traceEvents`` format that chrome://tracing and Perfetto load directly.
Device-side timing is XLA's own profiler: ``set_config(profile_device=True,
logdir=...)`` brackets the run with ``jax.profiler.start_trace`` /
``stop_trace`` so per-kernel HLO timing lands in TensorBoard/Perfetto too.
``profile_sync=True`` makes each dispatch block until the result is ready,
turning dispatch spans into true op latencies (the reference's engine records
completion times the same way — at the cost of killing async overlap, so
only for profiling runs).
"""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

from . import telemetry as _telemetry

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "reset", "Task", "Frame", "Event", "Counter",
           "Marker", "scope", "scope_cost", "counter_value", "counters",
           "counters_clear"]

_lock = threading.Lock()


class _ProfilerState:
    def __init__(self):
        self.active = False          # fast-path flag read by invoke
        self.paused = False
        self.sync = False
        self.filename = "profile.json"
        self.aggregate = True
        self.device = False
        self.logdir = None
        self.continuous_dump = False
        self.events = []             # chrome trace events
        self.stats = {}              # name -> [count, total_s, min_s, max_s]
        self._started_jax_trace = False


_P = _ProfilerState()
# module-level alias read on the invoke hot path (None = off)
ACTIVE = False


def _now_us():
    return time.perf_counter() * 1e6


def set_config(filename="profile.json", profile_all=False,
               profile_symbolic=True, profile_imperative=True,
               profile_api=False, profile_memory=False,
               aggregate_stats=True, continuous_dump=False,
               profile_sync=False, profile_device=False, logdir=None,
               **kwargs):
    """Configure output path and modes (ref: profiler.set_config).

    Unknown legacy kwargs are accepted and ignored (the reference has ~15
    engine-specific knobs with no TPU meaning)."""
    with _lock:
        _P.filename = filename
        _P.aggregate = aggregate_stats or profile_all
        _P.sync = profile_sync
        _P.device = profile_device or (logdir is not None)
        _P.logdir = logdir or (os.path.splitext(filename)[0] + "_xla")
        _P.continuous_dump = continuous_dump


def set_state(state="stop"):
    """'run' | 'stop' (ref: profiler.set_state)."""
    global ACTIVE
    import sys
    dump_after = False
    with _lock:
        if state == "run":
            _P.active, _P.paused = True, False
            # install the dispatch hook (kept out of the package's import
            # graph so an idle profiler costs the hot path nothing)
            from .ndarray import ndarray as _nd_mod
            _nd_mod._PROF = sys.modules[__name__]
            if _P.device and not _P._started_jax_trace:
                try:
                    import jax
                    jax.profiler.start_trace(_P.logdir)
                    _P._started_jax_trace = True
                except Exception:
                    pass
        elif state == "stop":
            _P.active = False
            if _P._started_jax_trace:
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                _P._started_jax_trace = False
            dump_after = _P.continuous_dump
        else:
            raise ValueError("state must be 'run' or 'stop'")
        ACTIVE = _P.active and not _P.paused
    if dump_after:  # outside _lock — dump() re-acquires it
        dump()


def start():
    set_state("run")


def stop():
    set_state("stop")


def pause(*a, **k):
    global ACTIVE
    with _lock:
        _P.paused = True
        ACTIVE = False


def resume(*a, **k):
    global ACTIVE
    with _lock:
        _P.paused = False
        ACTIVE = _P.active


def reset():
    with _lock:
        _P.events.clear()
        _P.stats.clear()


# ------------------------------------------------------------- recording --
def record_span(name, t0_us, t1_us, cat="operator"):
    """Append one completed span (µs timestamps) + aggregate it."""
    dur = t1_us - t0_us
    ev = {"name": name, "ph": "X", "ts": t0_us, "dur": dur,
          "pid": os.getpid(), "tid": threading.get_ident(), "cat": cat}
    with _lock:
        _P.events.append(ev)
        if _P.aggregate:
            s = _P.stats.get(name)
            if s is None:
                _P.stats[name] = [1, dur, dur, dur]
            else:
                s[0] += 1
                s[1] += dur
                s[2] = min(s[2], dur)
                s[3] = max(s[3], dur)


def want_sync():
    return _P.sync


class scope:
    """``with profiler.scope("name"):`` — the program's thread-bound span.
    One ``with``, one name, three records:

    - always a ``jax.profiler.TraceAnnotation(name)``, so ANY jax profiler
      session (``mx.profiler``'s, a benchmark's ``start_trace``, a
      TensorBoard capture) shows the region on the thread that ran it,
      beside the device's events.  With no session active it costs a flag
      test (``scope_cost()``);
    - while ``telemetry`` is armed, a ``telemetry.Span`` of the same name
      in its store, child of the enclosing scope on this thread
      (``telemetry.open_scope``); ``set(**attrs)`` adds attributes to it.
      ``telemetry.enable(sample=...)`` samples root scopes, and what is
      nested in them, out; ``cat="setup"`` marks a scope that runs once a
      process or once a signature (``TrainStep.deferred_init``,
      ``.state_init``, ``.compile``), which is kept whatever ``sample``
      says;
    - while ``mx.profiler`` runs, an event in its Chrome-trace buffer.

    The program's names are ``<Component>.<phase>`` with the component in
    CamelCase or a server's own name (``TrainStep.step``,
    ``DevicePrefetcher.device_put``, ``<server name>.decode``); jax's own
    host events never have that form (``PjitFunction(f)``, ``$file:line``).
    """

    __slots__ = ("_name", "_cat", "_attrs", "_ann", "_t0", "_t1", "_token")
    # Task/Frame/Event start() and stop() need not nest, which a stack of
    # enclosing scopes cannot follow: they keep out of the span store
    _STORE = True

    def __init__(self, name, cat="region", **attrs):
        self._name = name
        self._cat = cat
        self._attrs = attrs or None
        self._token = None

    def __enter__(self):
        self._ann = _Annotation(self._name)
        self._ann.__enter__()
        if _telemetry.ACTIVE and self._STORE:
            self._token = _telemetry.open_scope(
                self._name, self._attrs, keep=self._cat == "setup")
        self._t0 = _now_us()
        return self

    def set(self, **attrs):
        """Attributes learned inside the region (in-memory span only: an
        annotation's are fixed when it opens)."""
        if self._token is not None and isinstance(
                self._token[1], _telemetry.Span):
            self._token[1].attrs.update(attrs)

    @property
    def seconds(self):
        """Length of the closed region, from the span's own two stamps."""
        return (self._t1 - self._t0) / 1e6

    def __exit__(self, exc_type, exc, tb):
        t1 = self._t1 = _now_us()
        self._ann.__exit__(exc_type, exc, tb)
        if self._token is not None:     # exporting is not part of the region
            _telemetry.close_scope(self._token, t1, error=exc_type)
            self._token = None
        if ACTIVE:
            record_span(self._name, self._t0, t1, self._cat)


def scope_cost(iters=100_000):
    """Measured seconds per ``with scope(...)`` right now; with no profiler
    session, ``mx.profiler`` stopped and telemetry off that is the dark
    cost every instrumented site pays (``telemetry.guard_cost``'s idiom)."""
    t0 = time.perf_counter()
    for _ in range(iters):
        with scope("Profiler.scope_cost"):
            pass
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------------------- output --
def dump(finished=True):
    """Write the Chrome-trace JSON to the configured filename.  Events
    are sorted by timestamp (a span is appended when it closes, so an
    outer one follows its children) so ``ts`` is monotonic per tid in the
    written stream."""
    with _lock:
        payload = {"traceEvents": sorted(_P.events,
                                         key=lambda e: e.get("ts", 0)),
                   "displayTimeUnit": "ms"}
    d = os.path.dirname(_P.filename)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(_P.filename, "w") as f:
        json.dump(payload, f)


def dumps(reset=False):
    """Aggregate per-op statistics table (ref: profiler.dumps)."""
    with _lock:
        rows = sorted(_P.stats.items(), key=lambda kv: -kv[1][1])
        out = ["Profile Statistics:",
               f"{'Name':<40s}{'Count':>8s}{'Total(ms)':>12s}"
               f"{'Min(ms)':>10s}{'Max(ms)':>10s}{'Avg(ms)':>10s}"]
        for name, (cnt, tot, mn, mx) in rows:
            out.append(f"{name:<40s}{cnt:>8d}{tot / 1e3:>12.3f}"
                       f"{mn / 1e3:>10.3f}{mx / 1e3:>10.3f}"
                       f"{tot / cnt / 1e3:>10.3f}")
        if reset:
            _P.stats.clear()
    return "\n".join(out)


# ----------------------------------------------- instrumentation objects --
class Domain:
    """Grouping namespace for custom objects (ref: profiler.Domain)."""

    def __init__(self, name):
        self.name = name


class Task(scope):
    """Named task span (ref: profiler.Task). start()/stop() API."""

    _STORE = False

    def __init__(self, domain=None, name="task"):
        super().__init__(name if domain is None
                         else f"{getattr(domain, 'name', domain)}::{name}",
                         cat="task")

    def start(self):
        self.__enter__()

    def stop(self):
        self.__exit__(None, None, None)


class Frame(Task):
    """Frame span (ref: profiler.Frame) — same mechanics, 'frame' category."""

    def __init__(self, domain=None, name="frame"):
        Task.__init__(self, domain, name)
        self._cat = "frame"


class Event(Task):
    """ref: profiler.Event."""

    def __init__(self, name="event"):
        Task.__init__(self, None, name)
        self._cat = "event"


_COUNTERS = {}   # name -> most recent Counter instance (see counter_value)


def counter_value(name, default=None):
    """Current value of the most recently created Counter named ``name``,
    or ``default`` when none exists.  Values track regardless of profiler
    state (only trace EMISSION is gated on ACTIVE), so health counters
    like ``TrainStep::nonfinite_skips`` are readable in production runs
    with the profiler off."""
    c = _COUNTERS.get(name)
    return default if c is None else c._value


def counters(prefix=None):
    """``{name: value}`` snapshot over the live Counters, optionally
    filtered to names starting with ``prefix``.  Like ``counter_value``,
    reads regardless of profiler state — a serving health endpoint polls
    ``counters("InferenceServer::")`` with the profiler off."""
    with _lock:
        items = list(_COUNTERS.items())
    return {n: c._value for n, c in items
            if prefix is None or n.startswith(prefix)}


def counters_clear(prefix=None):
    """Drop Counter registrations (all, or names starting with
    ``prefix``) from the ``counter_value``/``counters`` namespace AND
    from the telemetry registry backing them.

    A serving fleet creates one counter series per replica under its
    own name prefix; a restarted fleet (or a test building several)
    reuses those names, and without this the snapshot would keep
    reporting the dead instance's values until the new one's first
    write.  Live ``Counter`` objects keep working against their own
    (now detached) gauge — only the name→value namespaces forget
    them."""
    with _lock:
        names = [n for n in _COUNTERS
                 if prefix is None or n.startswith(prefix)]
        for name in names:
            del _COUNTERS[name]
    reg = _telemetry.registry()
    for name in names:
        reg.remove(name)


class Counter:
    """Numeric counter series (ref: profiler.Counter).

    ISSUE 13: the value lives in a ``telemetry.Gauge`` of the shared
    ``telemetry.registry()`` under the same series name — the profiler
    snapshot (``counters``/``counter_value``) and the telemetry
    expositions read the SAME cell, so the two systems can never report
    different values for one series.  Creating a Counter under an
    existing name gives the series a FRESH cell starting at ``value``
    (the fresh-instance semantics fleet restarts rely on) — a stale
    same-named instance keeps writing its own detached gauge, so a
    replaced server's background threads can never bleed increments
    into the replacement's live series."""

    def __init__(self, domain=None, name="counter", value=0):
        self.name = (name if domain is None
                     else f"{getattr(domain, 'name', domain)}::{name}")
        reg = _telemetry.registry()
        reg.remove(self.name)
        self._gauge = reg.gauge(self.name)
        self._gauge.set(value)
        _COUNTERS[self.name] = self

    @property
    def _value(self):
        return self._gauge.value

    def _emit(self):
        if not ACTIVE:
            return
        ev = {"name": self.name, "ph": "C", "ts": _now_us(),
              "pid": os.getpid(), "args": {"value": self._value}}
        with _lock:
            _P.events.append(ev)

    def set_value(self, value):
        self._gauge.set(value)
        self._emit()

    # increments are read-modify-write and counters are shared across
    # threads (serving sheds from every client thread) — the gauge's
    # own lock makes the update atomic; emit happens outside it
    def increment(self, delta=1):
        self._gauge.add(delta)
        self._emit()

    def decrement(self, delta=1):
        self._gauge.add(-delta)
        self._emit()


class Marker:
    """Instant marker (ref: profiler.Marker)."""

    def __init__(self, domain=None, name="marker"):
        self.name = (name if domain is None
                     else f"{getattr(domain, 'name', domain)}::{name}")

    def mark(self, scope="process"):
        if not ACTIVE:
            return
        ev = {"name": self.name, "ph": "i", "ts": _now_us(),
              "pid": os.getpid(), "tid": threading.get_ident(),
              "s": {"process": "p", "thread": "t",
                    "global": "g"}.get(scope, "p")}
        with _lock:
            _P.events.append(ev)
