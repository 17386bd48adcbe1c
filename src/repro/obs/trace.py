"""Host-side spans, the compile counter, the metric registry, and the
JSONL event sink.

The observability substrate for the whole GP inference stack
(DESIGN.md sec. 13, docs/observability.md).  Four pieces:

  * ``span(name)``  — nestable context managers.  Every span opens a
    ``jax.profiler.TraceAnnotation(name)`` whenever a profiler session
    is active, whatever the master switch says, so the program's host
    time lands on the profiler's clock beside the device ops; the
    profiler nests spans by time.  It never syncs the device or
    compiles anything.  With observability on, a completed span also
    observes a ``span.<path>.seconds`` histogram (nesting builds a
    dotted path: ``span("hmc.phase2")`` inside ``span("hmc.gpg_hmc")``
    records ``hmc.gpg_hmc.hmc.phase2``) and, when a sink is configured,
    appends one JSONL event.  While a profiler session is active the
    completed spans are also kept in memory (:func:`profiled`).
  * the compile counter — one ``jax.monitoring`` listener on XLA's
    backend-compile event adds each executable built (compiled, or
    loaded from the persistent cache) and its seconds to the innermost
    open span (``"none"`` outside any): :func:`compiles_by_span`.  With
    observability on it also feeds ``compile.span.<name>.{count,seconds}``.
  * ``Registry``    — a process-global store of counters (monotonic),
    gauges (last value) and histograms (count/total/min/max).  Cheap
    enough to be always-on internally; the *wiring call sites* across
    core/train/hyper are gated on :func:`enabled` so disabled mode costs
    one predicate per call.
  * JSONL sink      — ``configure(jsonl=path)`` (or the
    ``REPRO_OBS_JSONL`` env var) appends events as JSON lines;
    ``flush()`` writes a full registry snapshot event, and an atexit
    hook writes a final one, so ``tools/check_telemetry.py`` can gate a
    run from the log alone.

The master switch is the ``REPRO_OBS`` env var (default OFF) or
``set_enabled``/``use_obs``; it gates the registry and the sink, not the
profiler annotations.  Everything here is host-side python — nothing in
this module touches a jaxpr, and the in-jit taps (``repro.obs.injit``)
are trace-time no-ops when disabled, so compiled programs are
bit-identical with observability off (asserted in tests/test_obs.py).
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import threading
import time
from typing import Any, Iterator, Optional

import jax
from jax import monitoring as _monitoring
from jax._src import profiler as _jax_profiler

_ON_VALUES = ("1", "on", "true", "yes")

_FORCED: Optional[bool] = None
_LOCK = threading.RLock()
_TLS = threading.local()


# ---------------------------------------------------------------------------
# The master switch
# ---------------------------------------------------------------------------

_ENCODED: dict = {}


def _env(key: str) -> str:
    """``os.environ.get(key, "")`` without the KeyError that makes a
    missing key cost ~2 us: every span and call site reads the switch."""
    env = os.environ
    data = getattr(env, "_data", None)
    if data is None:
        return env.get(key, "")
    enc = _ENCODED.get(key)
    if enc is None:
        enc = _ENCODED[key] = env.encodekey(key)
    raw = data.get(enc)
    return "" if raw is None else env.decodevalue(raw)


def enabled() -> bool:
    """Whether observability is on: ``set_enabled`` override > REPRO_OBS."""
    if _FORCED is not None:
        return _FORCED
    return _env("REPRO_OBS").strip().lower() in _ON_VALUES


def set_enabled(on: Optional[bool]) -> None:
    """Force observability on/off; ``None`` restores env-var resolution."""
    global _FORCED
    _FORCED = None if on is None else bool(on)


@contextlib.contextmanager
def use_obs(on: bool = True) -> Iterator[None]:
    """Scoped ``set_enabled`` — the test suite's harness."""
    prev = _FORCED
    set_enabled(on)
    try:
        yield
    finally:
        set_enabled(prev)


# ---------------------------------------------------------------------------
# Registry: counters / gauges / histograms
# ---------------------------------------------------------------------------

class Hist:
    """count/total/min/max summary of an observed stream of scalars."""

    __slots__ = ("count", "total", "min", "max", "last")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.last = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        self.min = min(self.min, v)
        self.max = max(self.max, v)
        self.last = v

    def to_dict(self) -> dict:
        return {"count": self.count, "total": self.total,
                "min": self.min if self.count else 0.0,
                "max": self.max if self.count else 0.0, "last": self.last}


class Registry:
    """Process-global counters/gauges/histograms (thread-safe)."""

    def __init__(self):
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.hists: dict[str, Hist] = {}

    def inc(self, name: str, n: float = 1.0) -> None:
        with _LOCK:
            self.counters[name] = self.counters.get(name, 0.0) + float(n)

    def set_gauge(self, name: str, v: float) -> None:
        with _LOCK:
            self.gauges[name] = float(v)

    def observe(self, name: str, v: float) -> None:
        with _LOCK:
            h = self.hists.get(name)
            if h is None:
                h = self.hists[name] = Hist()
            h.observe(v)

    def snapshot(self) -> dict:
        with _LOCK:
            return {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "hists": {k: h.to_dict() for k, h in self.hists.items()},
            }

    def delta(self, before: dict) -> dict:
        """Registry change since a previous :meth:`snapshot` — counter and
        histogram count/total deltas (zero-delta counters dropped), gauges
        at their current values.  The per-bench ``telemetry`` sections of
        ``benchmarks/run.py`` are built from this."""
        cur = self.snapshot()
        b_c = before.get("counters", {})
        b_h = before.get("hists", {})
        counters = {k: v - b_c.get(k, 0.0) for k, v in cur["counters"].items()
                    if v - b_c.get(k, 0.0) != 0.0}
        hists = {}
        for k, h in cur["hists"].items():
            dc = h["count"] - b_h.get(k, {}).get("count", 0)
            if dc:
                hists[k] = {"count": dc,
                            "total": h["total"] - b_h.get(k, {}).get(
                                "total", 0.0),
                            "last": h["last"]}
        return {"counters": counters, "gauges": cur["gauges"],
                "hists": hists}

    def reset(self) -> None:
        with _LOCK:
            self.counters.clear()
            self.gauges.clear()
            self.hists.clear()


REGISTRY = Registry()


def counter_value(name: str) -> float:
    return REGISTRY.counters.get(name, 0.0)


def gauge_value(name: str, default: float = 0.0) -> float:
    return REGISTRY.gauges.get(name, default)


def snapshot() -> dict:
    return REGISTRY.snapshot()


def reset() -> None:
    REGISTRY.reset()


# ---------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------

_SINK = None            # open file object, or None
_SINK_PATH: Optional[str] = None
_SINK_EXPLICIT = False  # configure() beats the env var
_ATEXIT_ARMED = False


def configure(jsonl: Optional[str] = None) -> None:
    """Point the event sink at ``jsonl`` (append mode); ``None`` closes it
    and restores ``REPRO_OBS_JSONL`` env resolution."""
    global _SINK, _SINK_PATH, _SINK_EXPLICIT
    with _LOCK:
        if _SINK is not None:
            _SINK.close()
            _SINK = None
        _SINK_PATH = jsonl
        _SINK_EXPLICIT = jsonl is not None


def _get_sink():
    global _SINK, _SINK_PATH, _ATEXIT_ARMED
    with _LOCK:
        if _SINK is not None:
            return _SINK
        path = _SINK_PATH if _SINK_EXPLICIT else \
            os.environ.get("REPRO_OBS_JSONL") or None
        if not path:
            return None
        _SINK = open(path, "a", encoding="utf-8")
        if not _ATEXIT_ARMED:
            _ATEXIT_ARMED = True
            atexit.register(_final_flush)
        return _SINK


def emit(event: dict) -> None:
    """Append one event to the JSONL sink (no-op when disabled/unsinked)."""
    if not enabled():
        return
    sink = _get_sink()
    if sink is None:
        return
    event.setdefault("t", time.time())
    with _LOCK:
        sink.write(json.dumps(event, default=str) + "\n")
        sink.flush()


def flush() -> None:
    """Write a full registry snapshot event to the sink."""
    emit({"type": "snapshot", **REGISTRY.snapshot()})


def _final_flush() -> None:
    try:
        if enabled() and _get_sink() is not None:
            flush()
    except Exception:       # noqa: BLE001 — never fail interpreter exit
        pass


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

_ANNOTATION = jax.profiler.TraceAnnotation
# spans and compiles of the newest profiler session, bounded
_PROFILED_MAX = 1 << 17
_PROFILED_SPANS: collections.deque = collections.deque(maxlen=_PROFILED_MAX)
_PROFILED_COMPILES: collections.deque = collections.deque(
    maxlen=_PROFILED_MAX)
_SESSION = {"on": False, "session": None}


def _stack() -> list:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def _profiling() -> bool:
    """Whether a profiler session records host annotations now; a new
    session (``jax.profiler.start_trace``'s, where JAX shows it) empties
    what :func:`profiled` keeps."""
    on = _ANNOTATION.is_enabled()
    if on:
        state = getattr(_jax_profiler, "_profile_state", None)
        session = getattr(state, "profile_session", None)
        if not _SESSION["on"] or session is not _SESSION["session"]:
            with _LOCK:
                _SESSION["session"] = session
                _PROFILED_SPANS.clear()
                _PROFILED_COMPILES.clear()
    _SESSION["on"] = on
    return on


class span:
    """A nestable span: ``with span("state.extend"): ...``.

    Opens ``jax.profiler.TraceAnnotation(name)`` while a profiler session
    is active (the annotation records nothing otherwise, so it is not
    made), and keeps the thread's span stack, which the compile counter
    reads.  With observability on (:func:`enabled`), the monotonic
    duration goes into the ``span.<path>.seconds`` histogram plus one
    JSONL event.  Disabled and unprofiled, a span costs one to two
    microseconds.  ``as`` binds the dotted path when observability is on,
    else None."""

    __slots__ = ("name", "attrs", "_ann", "_t0", "_wall", "_path", "_ns")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> Optional[str]:
        stack = _stack()
        self._path = ".".join(stack + [self.name]) if enabled() else None
        stack.append(self.name)
        self._ann = None
        if _profiling():
            self._ns = (time.time_ns(), len(stack) - 1)
            self._ann = _ANNOTATION(self.name)
            self._ann.__enter__()
        if self._path is not None:
            self._wall = time.time()
            self._t0 = time.monotonic()
        return self._path

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
            start, depth = self._ns
            _PROFILED_SPANS.append((self.name, start, time.time_ns(), depth))
        _stack().pop()
        path = self._path
        if path is None:
            return
        dur = time.monotonic() - self._t0
        REGISTRY.observe(f"span.{path}.seconds", dur)
        ev = {"type": "span", "name": self.name, "path": path,
              "t": self._wall, "dur_s": dur}
        if self.attrs:
            ev["attrs"] = self.attrs
        emit(ev)


def profiled() -> dict:
    """The completed spans and the compiles of the newest profiler
    session, on the host's wall clock (``time.time_ns``), which the
    profiler's host annotations share up to its session's origin:

        {"spans": [(name, start_ns, end_ns, depth), ...],
         "compiles": [(innermost span, end_ns, seconds), ...]}
    """
    with _LOCK:
        return {"spans": list(_PROFILED_SPANS),
                "compiles": list(_PROFILED_COMPILES)}


# ---------------------------------------------------------------------------
# The compile counter
# ---------------------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES: dict[str, list] = {}


def _on_compile(event: str, duration: float, **_: Any) -> None:
    """Runs once per executable built, in the thread that asked for it."""
    if event != COMPILE_EVENT:
        return
    stack = getattr(_TLS, "stack", None)
    name = stack[-1] if stack else "none"
    with _LOCK:
        tot = _COMPILES.setdefault(name, [0, 0.0])
        tot[0] += 1
        tot[1] += float(duration)
        if _profiling():
            _PROFILED_COMPILES.append((name, time.time_ns(), float(duration)))
    if enabled():
        REGISTRY.inc(f"compile.span.{name}.count")
        REGISTRY.inc(f"compile.span.{name}.seconds", duration)


def compiles_by_span() -> dict:
    """``{span name: {"count": n, "seconds": s}}``: every executable the
    process built since it started, by the innermost span open at the
    time (``"none"`` outside any)."""
    with _LOCK:
        return {k: {"count": n, "seconds": sec}
                for k, (n, sec) in _COMPILES.items()}


_monitoring.register_event_duration_secs_listener(_on_compile)
