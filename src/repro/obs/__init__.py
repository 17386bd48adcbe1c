"""repro.obs — lightweight, jit-compatible observability (DESIGN.md 13).

Four pieces:

  trace          host-side spans, the compile counter, the metric
                 registry, the JSONL sink
  injit          ``jax.debug.callback`` taps from inside traced code
  compile_watch  recompile sentinel for jitted entry points
  health         condition/residual/precision-drift monitors

Spans always annotate: each ``span(name)`` opens a
``jax.profiler.TraceAnnotation(name)`` whenever a profiler session is
active, so a device trace shows the program's host time by name, and the
compile counter (``trace.compiles_by_span``) always attributes each
executable built to the innermost open span.  Neither syncs the device
nor compiles anything.

Everything else is gated on one switch (``REPRO_OBS`` env / ``use_obs``).
Disabled (the default) is near-zero-cost BY CONSTRUCTION: the registry
and sink call sites are one predicate, in-jit taps never enter the
jaxpr (trace-time gate), and ``compile_watch.wrap`` degenerates to a
plain ``jax.jit`` — compiled programs are bit-identical to a build
without the wiring (asserted in tests/test_obs.py).

Import as ``from repro.obs import trace as obs`` at call sites whose
namespace already uses the name ``trace`` (e.g. ``hyper/fit.py``).
"""
from . import trace, injit, compile_watch, health  # noqa: F401
from .trace import (  # noqa: F401
    REGISTRY, Registry, compiles_by_span, configure, counter_value, emit,
    enabled, flush, gauge_value, profiled, reset, set_enabled, snapshot,
    span, use_obs,
)
from .health import HealthMonitor  # noqa: F401
from .injit import tap, tap_metrics  # noqa: F401
