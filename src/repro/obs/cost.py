"""Cost accounting: modeled HBM bytes / flops as live per-call gauges.

``utils/hlo_cost.py`` and ``utils/roofline.py`` already model compiled
programs for the dry-run; this module turns them into *recorded telemetry*:

  * :func:`modeled`          — lower a callable once per (name, shape
    signature), run ``analyze_hlo`` on the compiled text, and publish
    ``cost.<name>.hbm_bytes`` / ``cost.<name>.out_bytes`` /
    ``cost.<name>.flops`` gauges + one ``{"type": "cost"}`` event.
    The analysis is cached, so steady-state serving pays nothing.
  * :func:`record_measured`  — put the measured seconds next to the model:
    ``cost.<name>.seconds`` and ``cost.<name>.roofline_fraction`` (the
    roofline-predicted time for the modeled bytes/flops divided by the
    measured time — achieved fraction of the chip's roofline bound,
    logged instead of folklore).

The serve layer calls both per request signature
(``train/serve.py::GPServeBundle.query``), scaling the one-chunk model by
the chunk count.  Lowering goes through a FRESH ``jax.jit`` of the raw
function — never through a CompileWatch-wrapped entry point, which would
record a phantom compile event.
"""
from __future__ import annotations

from typing import Callable, Optional

from repro.obs import compile_watch as _cw
from repro.obs import trace as _trace

_MODEL_CACHE: dict = {}


def modeled(name: str, fn: Callable, *args, scale: float = 1.0):
    """Model one call of ``fn(*args)``; publish ``cost.<name>.*`` gauges.

    Returns the (scaled) ``utils.hlo_cost.Costs`` — or None when
    observability is off (nothing is compiled or recorded).  Results are
    cached per (name, signature): the lower+compile+parse happens once
    per serve geometry, not per request.
    """
    if not _trace.enabled():
        return None
    import jax

    from repro.utils.hlo_cost import analyze_hlo

    key = (name, _cw.signature(args, {}))
    costs = _MODEL_CACHE.get(key)
    if costs is None:
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        costs = analyze_hlo(hlo)
        _MODEL_CACHE[key] = costs
    out = costs.scaled(scale) if scale != 1.0 else costs
    _trace.REGISTRY.set_gauge(f"cost.{name}.hbm_bytes", out.bytes_hbm)
    _trace.REGISTRY.set_gauge(f"cost.{name}.out_bytes", out.bytes_out)
    _trace.REGISTRY.set_gauge(f"cost.{name}.flops", out.flops)
    _trace.emit({"type": "cost", "name": name, "flops": out.flops,
                 "hbm_bytes": out.bytes_hbm, "out_bytes": out.bytes_out,
                 "scale": scale})
    return out


def record_measured(name: str, seconds: float, costs=None,
                    chip=None) -> Optional[float]:
    """Record measured wall-clock next to the model for ``name``.

    ``costs`` is a ``Costs`` from :func:`modeled` (pass the same one the
    request was modeled with); with it, the achieved fraction of roofline
    — min-time-per-model / measured — is computed against ``chip``
    (default: the peak-table entry of the TPU this process runs on,
    ``utils.roofline.chip_for``, which raises for an unknown TPU) and
    published as ``cost.<name>.roofline_fraction``.  Off a TPU, with no
    explicit ``chip``, no fraction is published: a host wall time over a
    TPU's peaks is not a roofline share.  Returns the fraction (or None).
    """
    if not _trace.enabled():
        return None
    _trace.REGISTRY.set_gauge(f"cost.{name}.seconds", float(seconds))
    _trace.REGISTRY.observe(f"cost.{name}.seconds_hist", float(seconds))
    frac = None
    if costs is not None and seconds > 0.0:
        if chip is None:
            import jax

            from repro.utils.roofline import chip_for

            dev = jax.devices()[0]
            if dev.platform != "tpu":
                return None
            chip = chip_for(dev.device_kind)
        bound = max(costs.bytes_hbm / chip.hbm_bw,
                    costs.flops / chip.peak_flops)
        frac = bound / float(seconds)
        _trace.REGISTRY.set_gauge(f"cost.{name}.roofline_fraction", frac)
    return frac


def clear_model_cache() -> None:
    _MODEL_CACHE.clear()
