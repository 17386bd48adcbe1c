#!/usr/bin/env python3
"""Smoke run of the streaming gradient-GP system on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the D-sharded state on four chips

One chip, through the entry points a user calls:

  main   ``GPGState("rbf", d=2**24, window=8)`` in float32 streams 12
         gradient observations of a seeded smooth function (past the
         window, so ``evict`` runs), serves 4 requests of 8 queries through
         ``build_gp_serve_step(state, microbatch=8)`` between the extends,
         and refits.  Every request is also answered by an exact Woodbury
         solve of the state's window on the plain-jnp backend, in float64
         on the host CPU (``oracle_posterior``), and the posterior value
         and gradient are held to ``REL_BOUND_JNP`` against it.
  dense  the same protocol at D = 1024, held to ``REL_BOUND_DENSE`` against
         the dense O((ND)^3) oracle ``core.woodbury.dense_solve`` (host).
  fleet  a 16-tenant ``GPFleetServer`` at d = 4096, window 8: two rounds of
         64 mixed extend/query requests, each drained; two tenants are
         replayed on a per-tenant ``GPGState`` and held to
         ``REL_BOUND_FLEET``.

``--chips 4`` runs only the sharded path: ``ShardedGPGState`` at d = 2**25
over ``make_d_mesh(4)``, 10 extends, ``posterior`` with and without the
ring pipeline, ``refit``; checked against the float64 host oracle on the
same window (see ``run_sharded`` for why not a one-chip ``GPGState``).

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
All data is generated on the device from ``--seed``.  The script exits
non-zero before any phase when JAX finds no TPU, and a missed bound or a
failed phase exits non-zero: nothing falls back to the CPU, to interpret
mode or to the jnp backend.  Data is float32 whatever ``jax_enable_x64``
says.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Relative bounds (normwise, ||got - want|| / ||want||) and why.
# main vs the float64 jnp oracle: the chip side accumulates D = 2**24
# products in f32; the Gram solve amplifies that by its conditioning (the
# 1% noise floor keeps it below ~1e2).  1e-3 is also the pallas-vs-jnp
# Woodbury parity the test suite holds at small D
# (tests/test_backend_dispatch.py).
REL_BOUND_JNP = 1e-3
# dense oracle at D = 1024: a float64 LU of the (ND, ND) Gram on the host;
# the chip side's f32 error ~ cond * eps_f32 with cond ~1e2 here.
REL_BOUND_DENSE = 1e-3
# fleet lane vs a per-tenant GPGState: the same kernels at the same shapes,
# vmapped; only CG stopping and summation order differ.
REL_BOUND_FLEET = 1e-4
# four-chip ShardedGPGState (float32 direct Woodbury solve off psummed
# strips) vs the float64 host oracle: as REL_BOUND_JNP.
REL_BOUND_SHARDED = 1e-3

KERNEL = "rbf"
NOISE_FRAC = 1e-2   # observation noise: 1% of the prior gradient variance


class SmokeFailure(AssertionError):
    """A phase missed one of its bounds."""


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def rel_err(got, want) -> float:
    """Normwise relative error, in float64 on the host (the two sides may
    live on different devices)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def require(record: dict) -> None:
    """Raise when a phase record holds a missed bound or a recompile."""
    bad = [k for k, (err, bound) in record.get("bounds", {}).items()
           if not err <= bound]
    if record.get("recompiles", 0):
        bad.append(f"recompiles={record['recompiles']}")
    if bad:
        raise SmokeFailure(f"phase {record['phase']!r} failed: {bad}")


# ---------------------------------------------------------------------------
# Instrumentation
# ---------------------------------------------------------------------------


class Compiles:
    """Counts XLA backend compilations (``jax.monitoring`` events)."""

    n = 0
    _armed = False

    @classmethod
    def arm(cls) -> None:
        if cls._armed:
            return
        from jax import monitoring

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.n += 1

        monitoring.register_event_duration_secs_listener(listener)
        cls._armed = True


@contextlib.contextmanager
def timed(out: dict, key: str):
    """Wall seconds (the caller blocks on its outputs inside) and the XLA
    compiles of the block, appended to ``out[key]`` / ``out[key+'_xla']``."""
    c0, t0 = Compiles.n, time.perf_counter()
    yield
    out.setdefault(key, []).append(time.perf_counter() - t0)
    out.setdefault(key + "_xla", []).append(Compiles.n - c0)


def ready(x):
    return jax.block_until_ready(x)


def n_watches() -> int:
    from repro.obs import compile_watch

    return len(compile_watch.all_watches())


def watch_recompiles(since: int) -> int:
    """Recompiled signatures of the compile watches made after ``since``."""
    from repro.obs import compile_watch

    return sum(len(w.violations())
               for w in compile_watch.all_watches()[since:])


def peak_bytes(device=None):
    dev = jax.devices()[0] if device is None else device
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def steady(xs):
    """(first, median of the rest) of a list of timings."""
    rest = sorted(xs[1:])
    return xs[0], (rest[len(rest) // 2] if rest else None)


# ---------------------------------------------------------------------------
# Seeded data, generated on the device
# ---------------------------------------------------------------------------


def make_problem(d: int, seed: int):
    """f(x) = sum(a * sin(x + b)): returns (observe(i), queries(i, x, q)).

    ``observe(i)`` is the i-th input x_i ~ N(0, I) with its gradient
    a * cos(x_i + b); ``queries(i, x, q)`` is a (q, d) batch 0.3 away (per
    coordinate) from ``x``.  The (d,)-sized work runs on the device, with
    ``a`` and ``b`` passed as arguments (a jit closure over them would
    embed them in the executable)."""
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32)
    b = 6.283185307179586 * jax.random.uniform(jax.random.fold_in(key, 2),
                                               (d,), jnp.float32)

    def observe(i):
        return _observation(key, a, b, i)

    def queries(i, around, q):
        return _queries(key, i, around, q)

    return observe, queries


@jax.jit
def _observation(key, a, b, i):
    x = jax.random.normal(jax.random.fold_in(key, 1000 + i), a.shape,
                          jnp.float32)
    return x, a * jnp.cos(x + b)


@functools.partial(jax.jit, static_argnums=3)
def _queries(key, i, around, q):
    z = jax.random.normal(jax.random.fold_in(key, 5000 + i),
                          (q,) + around.shape, jnp.float32)
    return around[None, :] + 0.3 * z


def hypers(d: int) -> dict:
    """Isotropic Lambda = I/d puts r = lam |x - x'|^2 at O(1) for N(0, I)
    inputs; the noise is NOISE_FRAC of the prior gradient variance lam."""
    lam = 1.0 / d
    return {"lam": lam, "noise": NOISE_FRAC * lam}


@contextlib.contextmanager
def reference_mode():
    """The oracle side: the plain-jnp backend in float64 on the host CPU.

    Not on the TPU: there XLA's float32 dot over a 2**24-long contraction
    came out 2.2e-3 (relative) off a chunked sum even at
    ``precision=highest`` (v5e).  Not in float32: the exact Woodbury solve
    of a 3-point window is 3e-4 off at D = 2**20 in float32 (its (N^2,
    N^2) inner system is ill-conditioned there) and ~1e-10 in float64.
    Arrays handed to the oracle go through :func:`to_ref`."""
    from repro.core import use_backend

    with use_backend("jnp"), jax.enable_x64(True), \
            jax.default_device(jax.devices("cpu")[0]):
        yield


def to_ref(*arrays):
    """Copy arrays to the host CPU as float64 (call inside reference_mode)."""
    cpu = jax.devices("cpu")[0]
    out = tuple(jnp.asarray(jax.device_put(a, cpu), jnp.float64)
                for a in arrays)
    return out if len(out) > 1 else out[0]


def oracle_posterior(spec, X, G, lam, noise, Xq):
    """Posterior means at Xq from an exact Woodbury solve of (X, G): the
    plain-jnp backend in float64 on the host CPU, independent of the state
    machine (no bordered Cholesky, no CG) and of the Pallas kernels."""
    from repro.core import build_factors, woodbury_solve
    from repro.core.query import posterior_batch

    with reference_mode():
        X, G, Xq = to_ref(X, G, Xq)
        f = build_factors(spec, X, lam=lam, noise=noise)
        return ready(posterior_batch(spec, Xq, f, woodbury_solve(spec, f, G)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def run_main(d: int = 2**24, *, window: int = 8, n_obs: int = 12,
             n_requests: int = 4, q: int = 8, refit_steps: int = 5,
             seed: int = 0) -> dict:
    """GPGState + build_gp_serve_step + refit, checked per request against
    :func:`oracle_posterior` on the state's window."""
    from repro.core import GPGState
    from repro.obs import trace as obs
    from repro.resilience import guardrails
    from repro.train.serve import build_gp_serve_step

    hp = hypers(d)
    observe, queries = make_problem(d, seed)
    w0 = n_watches()
    st = GPGState(KERNEL, d, window=window, dtype=jnp.float32, **hp)
    bundle = build_gp_serve_step(st, microbatch=q)
    every = max(1, n_obs // n_requests)
    t: dict = {}
    per_extend, errs = [], {"value": 0.0, "grad": 0.0}
    fallback0 = obs.counter_value("state.refactor_fallback")
    resil0 = {k: v for k, v in obs.snapshot().get("counters", {}).items()
              if k.startswith("resilience.")}
    check_finite_s = None
    for i in range(n_obs):
        x, g = ready(observe(i))
        if check_finite_s is None:
            t0 = time.perf_counter()
            guardrails.check_finite(x, g, what="observation")
            check_finite_s = time.perf_counter() - t0
        with timed(t, "extend"):
            ready(st.extend(x, g).data.Z)
        s = st.stats
        per_extend.append({"n": s["n"], "n_refactor": s["n_refactor"],
                           "cg_iters": s["cg_iters"],
                           "resnorm": s["resnorm"]})
        if (i + 1) % every == 0:
            Xq = queries(i, x, q)
            with timed(t, "query"):
                pb = ready(bundle.query(Xq))
            pr = oracle_posterior(st.spec, st.X, st.G, hp["lam"],
                                  st._noise_eff, Xq)
            errs["value"] = max(errs["value"], rel_err(pb.value, pr.value))
            errs["grad"] = max(errs["grad"], rel_err(pb.grad, pr.grad))
    with timed(t, "refit"):
        res = st.refit(steps=refit_steps)
        ready(st.data.Z)
    Xq = queries(n_obs, x, q)
    with timed(t, "query"):
        pb = ready(bundle.query(Xq))
    # the oracle takes the fitted hypers as given: the comparison is of the
    # posterior path, not of the optimizer's sensitivity to precision
    pr = oracle_posterior(st.spec, st.X, st.G, float(st.data.lam),
                          st._noise_eff, Xq)
    err_refit = {"value": rel_err(pb.value, pr.value),
                 "grad": rel_err(pb.grad, pr.grad)}
    finite = bool(jnp.all(jnp.isfinite(pb.value))
                  & jnp.all(jnp.isfinite(pb.grad)))
    resil = {k: v - resil0.get(k, 0.0)
             for k, v in obs.snapshot().get("counters", {}).items()
             if k.startswith("resilience.") and v - resil0.get(k, 0.0)}
    f, Z = st.stream_factors
    hlo = jax.jit(bundle.step_fn).lower(
        f, Z, jnp.zeros((q, d), jnp.float32)).compile().as_text()
    ext_first, ext_steady = steady(t["extend"])
    q_first, q_steady = steady(t["query"])
    return {
        "phase": "main", "d": d, "window": window, "n_obs": n_obs,
        "q": q, "requests": len(t["query"]),
        "output_shapes": {"value": list(pb.value.shape),
                          "grad": list(pb.grad.shape)},
        "finite": finite,
        "compile_s": {"first_extend": ext_first, "first_query": q_first,
                      "refit_incl_compile": t["refit"][0]},
        "steady_s": {"extend_median": ext_steady,
                     "query_median": q_steady},
        "extend_s": t["extend"], "query_s": t["query"],
        "xla_compiles": {"extend": t["extend_xla"],
                         "query": t["query_xla"]},
        "recompiles": watch_recompiles(w0),
        "per_extend": per_extend,
        "degenerate_fallback": obs.counter_value("state.refactor_fallback")
        - fallback0,
        "resilience_counters": resil,
        "refit": {"steps": refit_steps, "lam": float(st.data.lam),
                  "noise": st.noise, "signal": st.signal,
                  "improvement": float(res.improvement)},
        "serve_step_tpu_custom_call": "tpu_custom_call" in hlo,
        "max_rel_err_vs_jnp": errs, "rel_err_vs_jnp_after_refit": err_refit,
        "check_finite_s": check_finite_s,
        "peak_bytes_in_use": peak_bytes(),
        "bounds": {"value_vs_jnp": (max(errs["value"], err_refit["value"]),
                                    REL_BOUND_JNP),
                   "grad_vs_jnp": (max(errs["grad"], err_refit["grad"]),
                                   REL_BOUND_JNP),
                   "finite": (0.0 if finite else 1.0, 0.0)},
    }


def run_dense(d: int = 1024, *, window: int = 8, n_obs: int = 12,
              q: int = 8, seed: int = 1) -> dict:
    """GPGState at small D against the dense (ND, ND) oracle.  The oracle
    side rebuilds everything from the state's window (X, G) in float64 on
    the host: none of the state's own factors enter it."""
    from repro.core import GPGState, build_factors
    from repro.core.query import posterior_batch
    from repro.core.woodbury import dense_solve

    hp = hypers(d)
    observe, queries = make_problem(d, seed)
    st = GPGState(KERNEL, d, window=window, dtype=jnp.float32, **hp)
    for i in range(n_obs):
        x, g = observe(i)
        st.extend(x, g)
    Xq = queries(n_obs, x, q)
    pb = ready(st.posterior(Xq))
    noise = st._noise_eff
    with reference_mode():
        X, G, Xq_ref = to_ref(st.X, st.G, Xq)
        f = build_factors(st.spec, X, lam=hp["lam"], noise=noise)
        Zd = dense_solve(st.spec, X, G, lam=hp["lam"], noise=noise)
        pd = ready(posterior_batch(st.spec, Xq_ref, f, Zd))
    err = {"Z": rel_err(st.Z, Zd), "value": rel_err(pb.value, pd.value),
           "grad": rel_err(pb.grad, pd.grad)}
    return {"phase": "dense", "d": d, "n": st.n, "q": q,
            "rel_err_vs_dense": err,
            "bounds": {f"{k}_vs_dense": (v, REL_BOUND_DENSE)
                       for k, v in err.items()}}


def run_fleet(d: int = 4096, *, tenants: int = 16, window: int = 8,
              q: int = 8, rounds: int = 2, compare: int = 2,
              seed: int = 2) -> dict:
    """GPFleetServer: mixed extend/query traffic, drained; lanes checked
    against per-tenant GPGState replays."""
    from repro.configs.paper_gp import GPFleetConfig
    from repro.core import GPGState
    from repro.train.serve import GPFleetServer

    hp = hypers(d)
    problems = [make_problem(d, seed * 1000 + k) for k in range(tenants)]
    w0 = n_watches()
    server = GPFleetServer(kernel=KERNEL, d=d, dtype=jnp.float32,
                           config=GPFleetConfig(batch=tenants,
                                                window=window))
    for k in range(tenants):
        server.connect(k, **hp)
    replay = [GPGState(KERNEL, d, window=window, dtype=jnp.float32, **hp)
              for _ in range(compare)]
    t: dict = {}
    err, n_req, n_obs = 0.0, 0, [0] * tenants
    for rnd in range(rounds):
        # tenant k's round: 3 extends and one query, the query at position
        # 1 + k % 3 — so every drained step after the first mixes ops
        plan = []
        for k in range(tenants):
            ops = ["extend"] * 3
            ops.insert(1 + k % 3, "query")
            plan.append(ops)
        reqs = []
        for pos in range(4):
            for k in range(tenants):
                observe, queries = problems[k]
                if plan[k][pos] == "extend":
                    payload = observe(n_obs[k])
                    n_obs[k] += 1
                else:
                    x_last = observe(n_obs[k] - 1)[0]
                    payload = queries(n_obs[k], x_last, q)
                reqs.append((k, plan[k][pos], payload,
                             server.submit(k, plan[k][pos], payload)))
        n_req += len(reqs)
        with timed(t, "drain"):
            steps = server.drain()
            ready([r.result.grad for *_, r in reqs if r.op == "query"])
        for k, op, payload, r in reqs:
            if not r.done or isinstance(r.result, Exception):
                raise SmokeFailure(f"tenant {k} {op} ended {r.result!r}")
            if k >= compare:
                continue
            if op == "extend":
                replay[k].extend(*payload)
            else:
                want = replay[k].posterior(payload)
                err = max(err, rel_err(r.result.value, want.value),
                          rel_err(r.result.grad, want.grad))
    return {"phase": "fleet", "d": d, "tenants": tenants, "window": window,
            "requests": n_req, "drain_steps_last_round": steps,
            "compile_s": {"first_round_drain": t["drain"][0]},
            "steady_s": {"later_round_drains": t["drain"][1:]},
            "xla_compiles": {"drain": t["drain_xla"]},
            "recompiles": watch_recompiles(w0),
            "max_rel_err_vs_gpgstate": err,
            "peak_bytes_in_use": peak_bytes(),
            "bounds": {"lanes_vs_gpgstate": (err, REL_BOUND_FLEET)}}


def run_sharded(d: int = 2**25, *, ndev: int = 4, window: int = 8,
                n_obs: int = 10, q: int = 8, refit_steps: int = 5,
                seed: int = 3) -> dict:
    """ShardedGPGState over make_d_mesh(ndev), checked against
    :func:`oracle_posterior` on its window.  Not against a one-chip
    ``GPGState``: at d = 2**25 that state ran out of a v5e's 16 GB of HBM
    in its first CG solve (the eager state ops peak near 10 GB at
    d = 2**24)."""
    from repro.core import ShardedGPGState
    from repro.core.dist_state import PHASE_PSUMS
    from repro.launch.mesh import make_d_mesh
    from repro.utils.hlo import count_psums

    hp = hypers(d)
    observe, queries = make_problem(d, seed)
    mesh = make_d_mesh(ndev)
    w0 = n_watches()
    st = ShardedGPGState(KERNEL, d, window=window, mesh=mesh,
                         dtype=jnp.float32, **hp)
    t: dict = {}
    for i in range(n_obs):
        x, g = observe(i)
        with timed(t, "extend"):
            ready(st.extend(x, g).data.base.Z)
    Xq = queries(n_obs, x, q)
    with timed(t, "query"):
        pb = ready(st.posterior(Xq))
    with timed(t, "query_ring"):
        pr = ready(st.posterior(Xq, chunks=2))
    r0 = oracle_posterior(st.spec, st.X, st.G, hp["lam"], st._noise_eff, Xq)
    with timed(t, "refit"):
        res = st.refit(steps=refit_steps)
        ready(st.data.base.Z)
    with timed(t, "query"):
        pf = ready(st.posterior(Xq))
    r1 = oracle_posterior(st.spec, st.X, st.G, float(st.data.base.lam),
                          st._noise_eff, Xq)
    peaks = [peak_bytes(dv) for dv in mesh.devices.flat]
    nz = jnp.asarray(st._noise_eff)
    # psums per phase: jaxpr psum equations and compiled all-reduces,
    # against the PHASE_PSUMS contract
    programs = {"extend": (st._phase_raw("extend"),
                           (st.data, st._pad_cols(x), st._pad_cols(x), nz)),
                "evict": (st._phase_raw("evict"), (st.data, nz)),
                "query": (st._query_raw(q), (st.data, st._pad_cols(Xq)))}
    psums = {}
    for phase, (fn, args) in programs.items():
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        psums[phase] = {
            "jaxpr": count_psums(jax.make_jaxpr(fn)(*args)),
            "hlo_all_reduce": hlo.count(" all-reduce(")
            + hlo.count(" all-reduce-start("),
            "contract": PHASE_PSUMS[phase]}
    err = {"value": rel_err(pb.value, r0.value),
           "grad": rel_err(pb.grad, r0.grad),
           "ring_value": rel_err(pr.value, r0.value),
           "ring_grad": rel_err(pr.grad, r0.grad),
           "refit_value": rel_err(pf.value, r1.value),
           "refit_grad": rel_err(pf.grad, r1.grad)}
    return {"phase": "sharded", "d": d, "devices": ndev,
            "d_per_device": st.d_pad // ndev, "window": window,
            "n_obs": n_obs, "q": q,
            "compile_s": {"first_extend": t["extend"][0],
                          "first_query": t["query"][0],
                          "first_ring_query": t["query_ring"][0],
                          "refit_incl_compile": t["refit"][0]},
            "steady_s": {"extend_median": steady(t["extend"])[1],
                         "query_after_refit": t["query"][1]},
            "xla_compiles": {"extend": t["extend_xla"]},
            "recompiles": watch_recompiles(w0),
            "psums": psums,
            "refit_improvement": float(res.improvement),
            "peak_bytes_in_use_per_device": peaks,
            "rel_err_vs_oracle": err,
            "bounds": dict(
                {f"{k}_vs_oracle": (v, REL_BOUND_SHARDED)
                 for k, v in err.items()},
                **{f"psums_{k}": (float(abs(v["jaxpr"] - v["contract"])),
                                  0.0)
                   for k, v in psums.items()})}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def device_record(chips: int) -> dict:
    """Exit non-zero unless JAX sees at least ``chips`` TPU devices."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); no phase was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the D-sharded path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = device_record(args.chips)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import resolve_backend
    from repro.obs import trace as obs
    from repro.utils import compile_cache

    cache_dir = compile_cache.enable()
    backend = resolve_backend()
    emit({"phase": "device", **device, "jax": jax.__version__,
          "backend": backend,
          "jax_enable_x64": bool(jax.config.jax_enable_x64),
          "compile_cache": cache_dir})
    if backend != "pallas":
        sys.exit(f"chip_smoke: resolved backend is {backend!r}, not pallas")
    obs.set_enabled(True)        # compile_watch counts serve recompiles
    Compiles.arm()
    s = args.seed
    if args.chips == 4:
        rec = run_sharded(ndev=4, seed=s + 3)
        emit(rec)
        require(rec)
    else:
        rec = run_main(seed=s)
        emit(rec)
        require(rec)
        if not rec["serve_step_tpu_custom_call"]:
            raise SmokeFailure("the compiled serve step holds no "
                               "tpu_custom_call: the kernels did not run")
        for run, offset in ((run_dense, 1), (run_fleet, 2)):
            rec = run(seed=s + offset)
            emit(rec)
            require(rec)
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
