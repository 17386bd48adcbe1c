"""Faults planted under the timed path, for the check that ``correct``
sees them (an altered answer is altered in every row, as the check reads
a sample of each answer's rows): the tests run each at a tiny size on the CPU, and
``bench/control.py --fault <kind>`` reads them on the chip at the cell's
own size.  Each is planted on one system's instances after set-up, so
nothing outlives the run.

    state_unchanged   an extend that leaves the state as it was
    half_batch        half of a query batch answered, the rest repeated
    altered_grad      in every row, one gradient coordinate moved by 1 %
                      of the row's norm
    altered_value     every value moved by 1 % of itself
    stale_value       each answer's values from the previous answer

No cell spans chips, so none can leave out an exchange.
"""
from __future__ import annotations

import jax.numpy as jnp

KINDS = ("state_unchanged", "half_batch", "altered_grad", "altered_value",
         "stale_value")


def _fill(half, rows: int):
    """``half``'s answers, repeated up to ``rows`` rows."""
    rest = rows - half.grad.shape[0]
    return half._replace(
        value=jnp.concatenate([half.value, half.value[:rest]]),
        grad=jnp.concatenate([half.grad, half.grad[:rest]]))


def _alter_grad(pb):
    return pb._replace(grad=pb.grad.at[:, 0].add(
        0.01 * jnp.linalg.norm(pb.grad, axis=1)))


def _alter_value(pb):
    return pb._replace(value=1.01 * pb.value)


class _Stale:
    """Hands each answer the values of the one before it."""

    def __init__(self):
        self.last = {}

    def __call__(self, key, pb):
        prev = self.last.get(key, pb.value)
        self.last[key] = pb.value
        return pb._replace(value=prev)


def plant(kind: str, system) -> None:
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; one of {KINDS}")
    if hasattr(system, "bundle"):
        _plant_stream(kind, system)
    else:
        _plant_fleet(kind, system)


def _plant_stream(kind, system):
    if kind == "state_unchanged":
        system.state.extend = lambda x, g, **kw: system.state
        return
    query = system.bundle.query
    if kind == "half_batch":
        def served(Xq):
            return _fill(query(Xq[: Xq.shape[0] // 2]), Xq.shape[0])
    elif kind == "altered_grad":
        def served(Xq):
            return _alter_grad(query(Xq))
    elif kind == "altered_value":
        def served(Xq):
            return _alter_value(query(Xq))
    else:
        stale = _Stale()

        def served(Xq):
            return stale(0, query(Xq))
    system.bundle.query = served


def _plant_fleet(kind, system):
    server = system.server
    if kind == "state_unchanged":
        fleet = server.fleet
        fleet.extend = lambda obs: fleet
        return
    serve = server._serve_queries
    stale = _Stale()

    def served(reqs):
        if kind == "half_batch":
            rows = {id(r): r.payload.shape[0] for r in reqs}
            for r in reqs:
                r.payload = r.payload[: r.payload.shape[0] // 2]
            serve(reqs)
            for r in reqs:
                r.result = _fill(r.result, rows[id(r)])
            return
        serve(reqs)
        for r in reqs:
            if kind == "altered_grad":
                r.result = _alter_grad(r.result)
            elif kind == "altered_value":
                r.result = _alter_value(r.result)
            else:
                r.result = stale(r.tenant, r.result)
    server._serve_queries = served
