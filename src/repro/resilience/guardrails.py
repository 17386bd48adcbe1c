"""Numerical guardrails: admission checks, the jitter-escalation ladder,
the CG-divergence watchdog, and the bf16-drift trip-wire.

Every guardrail runs outside the guarded programs, driven from the host:
the guarded jitted programs are byte-for-byte the same jaxprs with
guardrails on or off (the zero-cost contract, asserted by
``bench_resilience`` with the same primitive-count technique as
``obs/injit.py``).  The admission check tests device-resident payloads
where they live, with one small program of its own and one bool read
back; host payloads are tested on the host.  Beyond that, the happy path
pays a handful of scalar device reads per mutation, and only while
guardrails are enabled.

Master switch: ``REPRO_GUARDRAILS`` env var ("1"/"on"/"true"/"yes"; the
default is ON — resilience is the point), overridable in-process with
:func:`set_enabled` / the :func:`use_guardrails` context manager, same
shape as ``obs.trace``.

The guardrail ladder (DESIGN.md sec. 17.2), triggered when a factor goes
non-finite or a solve diverges:

  rung 0   exact refactor at the state's own jitter (corrupted-factor
           case: X/G masters are fine, the Cholesky is not);
  rung k   exact refactor at jitter * 10^k (genuinely degenerate stream:
           duplicated observations, collapsed pivots) — the escalated
           jitter STAYS on the state, because the stream that needed it
           still does;
  give up  restore the original jitter, leave telemetry, raise nothing —
           the caller decides (serving degrades, tests fail loudly).

Every action increments ``resilience.*`` counters and emits a JSONL
event through ``obs.trace`` so ``tools/check_telemetry.py`` can gate
recovery behavior.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as _trace
from repro.resilience.errors import NonFiniteObservationError

_FORCED: Optional[bool] = None


def enabled() -> bool:
    """Guardrails master switch (default ON)."""
    if _FORCED is not None:
        return _FORCED
    return os.environ.get("REPRO_GUARDRAILS", "on").lower() in (
        "1", "on", "true", "yes")


def set_enabled(flag: Optional[bool]) -> None:
    """Force guardrails on/off in-process (None = back to the env var)."""
    global _FORCED
    _FORCED = flag


@contextmanager
def use_guardrails(flag: bool = True):
    prev = _FORCED
    set_enabled(flag)
    try:
        yield
    finally:
        set_enabled(prev)


def record_recovery(kind: str, **attrs) -> None:
    """One handled fault: bump the recovery counters + emit an event.

    The chaos accounting contract: the injector bumps
    ``resilience.faults_injected`` once per injected fault, every handler
    calls this exactly once per fault it detects-and-handles, and
    ``check_telemetry --expect-recovery`` gates the two counters equal.
    """
    _trace.REGISTRY.inc("resilience.faults_recovered")
    _trace.REGISTRY.inc(f"resilience.recovered.{kind}")
    _trace.emit({"type": "resilience", "action": "recovered",
                 "kind": kind, **attrs})


# ---------------------------------------------------------------------------
# Admission: non-finite observations never touch a factor
# ---------------------------------------------------------------------------


@jax.jit
def _all_finite(arrays):
    """Whether every element of every array is finite: one program over
    all of them, built once per tuple of shapes and dtypes."""
    return jnp.stack([jnp.isfinite(a).all() for a in arrays]).all()


def check_finite(*arrays, what: str = "observation",
                 tenant=None) -> None:
    """Reject non-finite payloads with a typed error BEFORE any factor op.

    Device-resident payloads (``jax.Array``s) are tested where they live,
    by one fused reduction over all of them, and one bool comes back to
    the host; nothing of the payload is copied.  Other payloads (numpy
    arrays, lists, scalars) are tested on the host.  Either way the check
    runs outside the guarded programs, so the extend and serve jaxprs are
    untouched.  With observability on, each call bumps
    ``resilience.admission.device`` and/or ``resilience.admission.host``
    by the paths it took.
    """
    if not enabled():
        return
    on_device = tuple(a for a in arrays if isinstance(a, jax.Array))
    on_host = [a for a in arrays
               if a is not None and not isinstance(a, jax.Array)]
    # launched before the host tests below, which then overlap it
    ok = _all_finite(on_device) if on_device else None
    if _trace.enabled():
        if on_device:
            _trace.REGISTRY.inc("resilience.admission.device")
        if on_host:
            _trace.REGISTRY.inc("resilience.admission.host")
    for a in on_host:
        if not np.all(np.isfinite(np.asarray(a, dtype=np.float64))):
            _reject(what, tenant)
    if ok is not None and not bool(ok):
        _reject(what, tenant)


def _reject(what: str, tenant) -> None:
    _trace.REGISTRY.inc("resilience.rejected_nonfinite")
    _trace.emit({"type": "resilience", "action": "reject_nonfinite",
                 "what": what,
                 **({"tenant": str(tenant)} if tenant else {})})
    raise NonFiniteObservationError(
        f"non-finite {what} rejected at admission"
        + (f" (tenant {tenant!r})" if tenant is not None else ""))


# ---------------------------------------------------------------------------
# Jitter-escalation ladder on degenerate / corrupted factorizations
# ---------------------------------------------------------------------------


def factor_ok(state, *, cond_limit: Optional[float] = None) -> bool:
    """Is the cached factorization serviceable?  Finite L diagonal,
    finite representers/residual, and (optionally) a condition-proxy
    bound from ``obs.health``."""
    data = state.data
    n = int(data.count)
    if n < 1:
        return True
    diag = jnp.diagonal(data.L)[:n]
    ok = bool(jnp.all(jnp.isfinite(diag))
              & jnp.all(jnp.isfinite(data.Z[:n]))
              & jnp.isfinite(data.resnorm))
    if not ok:
        return False
    if cond_limit is not None:
        from repro.obs.health import condition_proxy

        if condition_proxy(data) > cond_limit:
            return False
    return True


def heal_factorization(state, *, max_rungs: int = 3,
                       factor: float = 10.0,
                       cond_limit: Optional[float] = None) -> int:
    """Climb the jitter ladder until the factorization is serviceable.

    Returns the rung that healed (0 = plain exact refactor), or -1 when
    even jitter * factor**max_rungs could not produce finite factors (the
    original jitter is restored in that case).
    """
    base = state.jitter
    for rung in range(max_rungs + 1):
        state.jitter = base * (factor ** rung)
        state.refactor()
        _trace.REGISTRY.inc("resilience.jitter_escalations" if rung
                            else "resilience.refactor_heals")
        if factor_ok(state, cond_limit=cond_limit):
            _trace.emit({"type": "resilience", "action": "heal",
                         "rung": rung, "jitter": float(state.jitter),
                         "n": state.n})
            return rung
    state.jitter = base
    _trace.REGISTRY.inc("resilience.heal_failed")
    _trace.emit({"type": "resilience", "action": "heal_failed",
                 "max_jitter": base * factor ** max_rungs, "n": state.n})
    return -1


def after_mutation(state) -> bool:
    """Post-extend watchdog hook (called by ``GPGState.extend`` while
    guardrails are on): one fused scalar read of the fresh pivot +
    residual; on non-finite, climb the ladder and record the recovery.

    Returns True when a heal ran.  Triggers on NON-FINITE only — large
    residuals on a healthy stream are the iterative regime's business,
    and spurious jitter escalation would perturb exact-path answers.
    """
    data = state.data
    n = int(data.count)
    if n < 1:
        return False
    pivot = jnp.diagonal(data.L)[n - 1]
    if bool(jnp.isfinite(pivot) & jnp.isfinite(data.resnorm)):
        return False
    _trace.REGISTRY.inc("resilience.factor_faults")
    rung = heal_factorization(state)
    if rung >= 0:
        record_recovery("degenerate_factor", rung=rung, n=n)
    return True


# ---------------------------------------------------------------------------
# CG-divergence watchdog (the regime/iterative path)
# ---------------------------------------------------------------------------


def cg_diverged(resnorm, rhs_norm: float) -> bool:
    """Divergence predicate for an iterative solve: a non-finite residual
    or one that GREW past the zero-iteration residual (||b||) means the
    Krylov recurrence broke (poisoned warm start, indefinite operator) —
    falling back to the exact solver is the only honest answer."""
    rn = float(resnorm)
    if not np.isfinite(rn):
        return True
    return rhs_norm > 0.0 and rn > 10.0 * rhs_norm


# ---------------------------------------------------------------------------
# bf16-drift trip-wire
# ---------------------------------------------------------------------------


def bf16_tripwire(state, *, limit: float = 0.05, n_points: int = 4) -> bool:
    """Validate the cached bf16 stream copies against the f32 masters;
    drop the cache (forcing a fresh cast from the masters on the next
    query) when they are non-finite or drifted past ``limit``.

    Cheap: the finiteness scan is over the cached (cap, D) bf16 copy, and
    the drift probe is ``obs.health.precision_drift`` at ``n_points``
    stored inputs.  Returns True when the wire tripped.
    """
    if getattr(state, "precision", "f32") != "bf16" or state.n < 1:
        return False
    cache = getattr(state, "_stream_cache", None)
    tripped = False
    if cache is not None:
        f = cache[1]
        if not bool(jnp.all(jnp.isfinite(f.Xt.astype(jnp.float32)))):
            tripped = True
    if not tripped:
        from repro.obs.health import precision_drift

        drift = precision_drift(state, n_points=n_points)
        tripped = (not np.isfinite(drift)) or drift > limit
    if tripped:
        state._stream_cache = None
        _trace.REGISTRY.inc("resilience.bf16_recache")
        _trace.emit({"type": "resilience", "action": "bf16_recache",
                     "n": state.n})
        record_recovery("bf16_drift", n=state.n)
    return tripped
