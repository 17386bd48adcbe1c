"""Serving step builders: LM prefill/decode AND batched GP posterior query.

decode_* shapes lower `serve_step` — one new token against a KV cache of
seq_len — NOT train_step (assignment contract). The cache is donated so
steady-state decode is allocation-free.

``build_gp_serve_step`` is the posterior-inference analogue: a fixed-shape
jitted microbatch query step over a live ``GPGState`` (core/state.py).
The compiled step takes the state's factor arrays as *arguments*, so
interleaved ``extend()`` updates never recompile — the serve loop is
observe -> extend -> keep serving from the same compiled function.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import (SHAPES, ModelConfig, batch_specs, build_model,
                          set_activation_rules)
from repro.obs import compile_watch as _cw
from repro.obs import trace as _obs
from repro.resilience import guardrails as _guard
from repro.resilience.errors import (DeadlineExceededError,
                                     NonFiniteObservationError,
                                     RetryExhaustedError, ShedResponse,
                                     TenantQuarantinedError)

from .sharding import (batch_partition_specs, cache_partition_specs,
                       param_named_shardings, sanitize_spec_tree)


@dataclasses.dataclass
class ServeBundle:
    step: Callable
    abstract_params: Any
    abstract_inputs: tuple
    in_shardings: tuple
    model: Any


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, *,
                       shape: str = "prefill_32k") -> ServeBundle:
    model = build_model(cfg)
    set_activation_rules(mesh, cfg.seq_shard_activations)
    ss = SHAPES[shape]
    pa, axes = model.abstract()
    p_shard = param_named_shardings(mesh, axes, pa)
    ba = batch_specs(cfg, shape)
    b_pspecs = sanitize_spec_tree(batch_partition_specs(cfg, ba, mesh), ba,
                                  mesh)
    b_shard = {k: NamedSharding(mesh, v) for k, v in b_pspecs.items()}

    def fn(params, batch):
        return model.prefill(params, batch, ss.seq_len)

    cache_abs = jax.eval_shape(lambda: model.init_cache(ss.global_batch,
                                                        ss.seq_len))
    c_specs = sanitize_spec_tree(
        cache_partition_specs(cache_abs, mesh, ss.global_batch), cache_abs,
        mesh)
    c_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), c_specs,
        is_leaf=lambda x: isinstance(x, P))
    jitted = jax.jit(fn, in_shardings=(p_shard, b_shard),
                     out_shardings=(NamedSharding(mesh, P()), c_shard))
    return ServeBundle(step=jitted, abstract_params=pa,
                       abstract_inputs=(ba,), in_shardings=(p_shard, b_shard),
                       model=model)


def build_decode_step(cfg: ModelConfig, mesh: Mesh, *,
                      shape: str = "decode_32k",
                      donate: bool = True) -> ServeBundle:
    model = build_model(cfg)
    set_activation_rules(mesh, cfg.seq_shard_activations)
    ss = SHAPES[shape]
    pa, axes = model.abstract()
    p_shard = param_named_shardings(mesh, axes, pa)

    cache_abs = jax.eval_shape(lambda: model.init_cache(ss.global_batch,
                                                        ss.seq_len))
    c_specs = sanitize_spec_tree(
        cache_partition_specs(cache_abs, mesh, ss.global_batch), cache_abs,
        mesh)
    c_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), c_specs,
        is_leaf=lambda x: isinstance(x, P))
    tok_abs = jax.ShapeDtypeStruct((ss.global_batch,), "int32")
    pos_abs = jax.ShapeDtypeStruct((ss.global_batch,), "int32")
    from repro.models import batch_axes_of
    b_ax = batch_axes_of(mesh)
    import numpy as np
    b_shards = int(np.prod([mesh.shape[a] for a in b_ax]))
    tok_spec = P(b_ax) if ss.global_batch % b_shards == 0 and \
        ss.global_batch >= b_shards else P()
    tok_shard = NamedSharding(mesh, tok_spec)

    def fn(params, cache, tokens, pos):
        return model.decode(params, cache, tokens, pos)

    jitted = jax.jit(
        fn,
        in_shardings=(p_shard, c_shard, tok_shard, tok_shard),
        out_shardings=(NamedSharding(mesh, P()), c_shard),
        donate_argnums=(1,) if donate else (),
    )
    return ServeBundle(step=jitted, abstract_params=pa,
                       abstract_inputs=(cache_abs, tok_abs, pos_abs),
                       in_shardings=(p_shard, c_shard, tok_shard, tok_shard),
                       model=model)


# ---------------------------------------------------------------------------
# GP posterior query serving (core/state.py + core/query.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GPServeBundle:
    """A compiled batched-query endpoint over a live posterior state.

    ``query(Xq)`` pads the request up to a multiple of ``microbatch``,
    runs the jitted fixed-shape chunk step per microbatch against the
    CURRENT state revision (factors/Z are read per call), and trims the
    padding off. Zero solves per request; extend() between requests reuses
    the same executable.

    With ``return_std`` the step additionally takes a ``GramSolver``
    argument (the structured factorization of the noisy Gram, built once
    per request by ``refresh_solver``).  Every hyperparameter — lam,
    signal, noise — reaches the compiled step as an ARRAY inside the
    factor/solver pytrees, so a ``refit()`` between requests changes the
    numbers but never the shapes: hypers are dynamic arguments and the
    executable survives them.
    """

    state: Any                       # GPGState
    microbatch: int
    step: Callable                   # jitted (factors, Z[, solver], chunk[, probe])
    probe: Optional[jnp.ndarray]
    return_std: bool = False
    return_grad_std: bool = False
    step_fn: Optional[Callable] = None   # the raw (unjitted) step, for
    # lowering and inspection through a fresh jit, never through the
    # compile-watched entry point (a lowering is not a serve compile)
    _solver_cache: Any = None        # OrderedDict: revision key -> GramSolver
    # LU factorizations per cached revision are O(cap^4) floats — a
    # long-running server interleaving refit()/extend() with queries would
    # otherwise accrete one per revision forever, so the cache is a small
    # LRU: the common alternating-revision pattern still hits, memory is
    # bounded at _SOLVER_CACHE_MAX factorizations.
    _SOLVER_CACHE_MAX = 4

    def refresh_solver(self):
        """The variance solver for the CURRENT factor revision — factorized
        once per revision (O(N^2 D + (N^2)^3)) and LRU-cached.  The key is
        the state's ``factor_revision`` counter (+ the noise/signal
        hypers), NOT the identity of the data pytree: a mutation that
        rebuilds ``GPGData`` without touching the factorization (e.g. a
        ``resolve()`` against a new RHS) keeps the key and HITS, instead
        of silently re-factorizing and double-caching an identical LU.
        ``id(st)`` rides along (with an `is` check; the cached reference
        pins it) so a swapped-in replacement state can never collide."""
        import collections

        from repro.hyper.variance import make_solver

        st = self.state
        if self._solver_cache is None:
            self._solver_cache = collections.OrderedDict()
        key = (id(st), st.factor_revision, st.noise, st.signal)
        hit = self._solver_cache.get(key)
        if hit is not None and hit[0] is st:
            self._solver_cache.move_to_end(key)
            if _obs.enabled():
                _obs.REGISTRY.inc("serve.solver_cache.hits")
            return hit[1]
        if _obs.enabled():
            _obs.REGISTRY.inc("serve.solver_cache.misses")
        solver = make_solver(st.spec, st.padded_factors, noise=st.noise,
                             signal=st.signal, count=st.data.count)
        self._solver_cache[key] = (st, solver)
        while len(self._solver_cache) > self._SOLVER_CACHE_MAX:
            self._solver_cache.popitem(last=False)
            if _obs.enabled():
                _obs.REGISTRY.inc("serve.solver_cache.evictions")
        return solver

    def query(self, Xq):
        from repro.core.query import PosteriorBatch

        with _obs.span("serve.query"):
            if getattr(self.state, "_reduction", None) is not None:
                return self._query_reduced(Xq)
            return self._query(Xq, PosteriorBatch)

    def _query_reduced(self, Xq):
        """Serve through the state's own reduced-frame path (the bundle's
        compiled step was shaped for the raw frame).  grad_std cannot
        rotate through the reduction basis — degrade to a grad_std=None
        answer instead of killing the request (typed, counted)."""
        from repro.resilience.errors import UnsupportedQueryError

        try:
            return self.state.posterior(
                Xq, probe=self.probe, microbatch=self.microbatch,
                return_std=self.return_std,
                return_grad_std=self.return_grad_std)
        except UnsupportedQueryError:
            if _obs.enabled():
                _obs.REGISTRY.inc("resilience.degraded_query")
            _obs.emit({"type": "degraded_query", "want": "grad_std"})
            return self.state.posterior(
                Xq, probe=self.probe, microbatch=self.microbatch,
                return_std=self.return_std, return_grad_std=False)

    def _query(self, Xq, PosteriorBatch):
        Xq = jnp.atleast_2d(Xq)
        q, d = Xq.shape
        b = self.microbatch
        pad = (-q) % b
        # fixed-capacity padded views in the state's STREAM precision:
        # shapes are stable across extend(), so the compiled step is
        # reused (padding is exact for queries); with precision='bf16'
        # the bf16 copies are cached per revision by the state, so the
        # serve step streams half the bytes with no per-request cast.
        # probe/std endpoints serve from the unshifted f32 masters
        # (GramFactors.shift is a mean-path-only frame).
        want_std = self.return_std or self.return_grad_std
        if want_std or self.probe is not None:
            f, Z = self.state.padded_factors, self.state.data.Z
        else:
            f, Z = self.state.stream_factors
        if f.shift is not None:
            Xq = (Xq - f.shift).astype(f.Xt.dtype)
            f = f._replace(shift=None)
        elif f.c is not None and f.Xt.dtype == jnp.bfloat16:
            # dot-kernel bf16 stream: center-then-cast (the stored Xt is
            # centered; quantizing absolute coords first loses |x|/|x-c|)
            Xq = (Xq - f.c).astype(f.Xt.dtype)
            f = f._replace(c=None)
        Xp = jnp.pad(Xq.astype(f.Xt.dtype), ((0, pad), (0, 0)))
        solver = self.refresh_solver() if want_std else None
        if _obs.enabled():
            _obs.REGISTRY.inc("serve.requests")
            _obs.REGISTRY.inc("serve.points", q)
            _obs.REGISTRY.set_gauge("serve.queue_depth", (q + pad) // b)
        chunks = []
        for i in range(0, q + pad, b):
            args = (f, Z) + ((solver,) if want_std else ()) + (Xp[i:i + b],)
            if self.probe is not None:
                args = args + (self.probe,)
            chunks.append(self.step(*args))
        cat = lambda xs: jnp.concatenate(xs)[:q]
        out = PosteriorBatch(
            value=cat([c.value for c in chunks]),
            grad=cat([c.grad for c in chunks]),
            hess_v=None if self.probe is None else
            cat([c.hess_v for c in chunks]),
            std=cat([c.std for c in chunks]) if self.return_std or
            self.return_grad_std else None,
            grad_std=cat([c.grad_std for c in chunks])
            if self.return_grad_std else None,
        )
        return out


def build_gp_serve_step(state, *, microbatch: int | None = None, probe=None,
                        return_std: bool = False,
                        return_grad_std: bool = False,
                        precision: str | None = None,
                        config=None) -> GPServeBundle:
    """Compile a batched posterior query step for a ``GPGState``.

    One compilation per (microbatch, capacity, D) shape — the step is fed
    the state's fixed-capacity padded factor views, so extend()/evict()
    never change the compiled shapes (only an unbounded-growth capacity
    doubling does).  Q-query requests cost O(Q N D) with exactly zero
    inner solves (the solve happened at ``extend()`` time — factor reuse
    is the whole point of the state).

    ``return_std=True`` serves posterior value stds (``return_grad_std``
    gradient stds too) through one structured Gram factorization per
    request; the hypers ride inside the solver pytree, so refits between
    requests never recompile (asserted in tests/test_hyper.py).

    ``config`` (a ``repro.configs.paper_gp.GPServeConfig``) supplies
    defaults for ``microbatch`` and ``precision``; an explicit
    ``precision`` (or config) of 'bf16' switches the STATE's stream
    storage to bf16 — the per-revision bf16 copies live on the state, so
    every consumer of ``state.stream_factors`` shares them.  When a
    config is passed, its ``tol``/``maxiter`` solve knobs are applied to
    the state too (they shape the extend-time CG re-solves this bundle's
    queries are served from).
    """
    from repro.configs.paper_gp import GP_SERVE
    from repro.core.query import make_query_fn

    if config is not None and precision is None:
        precision = config.precision
    if microbatch is None:
        microbatch = (config or GP_SERVE).microbatch
    if config is not None:
        state.tol = float(config.tol)
        state.maxiter = config.maxiter
    if precision is not None:
        # precision lives on the STATE (shared by every bundle/consumer);
        # an explicit request here re-points all of them — see
        # GPGState.set_precision
        state.set_precision(precision)
    fn = make_query_fn(state.spec, with_probe=probe is not None,
                       with_std=return_std, with_grad_std=return_grad_std)
    if _obs.enabled():
        # pre-register the serve counters at 0 so a run's final snapshot
        # exports them even when never tripped (check_telemetry contract)
        for name in ("serve.solver_cache.hits", "serve.solver_cache.misses",
                     "serve.solver_cache.evictions", "serve.requests"):
            _obs.REGISTRY.inc(name, 0)
    # compile_watch.wrap IS jax.jit when observability is off (bit-
    # identical serve step); on, every trace is counted per signature and
    # the "extend/refit never recompile" contract becomes a runtime gate
    return GPServeBundle(
        state=state, microbatch=int(microbatch),
        step=_cw.wrap(fn, name="gp_serve_step"), step_fn=fn,
        probe=None if probe is None else jnp.asarray(probe),
        return_std=bool(return_std), return_grad_std=bool(return_grad_std),
    )


# ---------------------------------------------------------------------------
# Multi-tenant continuous batching (core/fleet.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetRequest:
    """One pending tenant op.  ``result`` is set when the request has been
    packed into a launch (``done`` flips true); queries resolve to a
    ``PosteriorBatch``, refits to the fitted mll, lifecycle ops to None.

    Failure outcomes complete the request too: ``result`` is then a typed
    ``ResilienceError`` instance (deadline/retry/quarantine) or a
    ``ShedResponse`` — callers branch on type, they never block forever.
    ``deadline``/``not_before`` are server STEP counts (the serve clock),
    not wall time; ``chaos_kind`` tags injector-corrupted requests so
    recovery accounting stays exact."""

    tenant: Any
    op: str                 # 'extend' | 'evict' | 'resolve' | 'refit' | 'query'
    payload: Any = None
    done: bool = False
    result: Any = None
    attempts: int = 0
    deadline: Optional[int] = None
    not_before: int = 0
    chaos_kind: Optional[str] = None


class GPFleetServer:
    """Continuous-batching front end over a :class:`~repro.core.GPFleet`.

    The vLLM-style serving loop for GP posteriors: tenants ``connect`` and
    ``submit`` ops asynchronously; each ``step()`` packs the queue's
    head-of-line requests (at most ONE per tenant, preserving per-tenant
    submission order) into per-op groups and fires ONE vmapped launch per
    op type present — so a step serving 50 tenants costs the same number
    of launches as a step serving one.  Query payloads are padded into
    power-of-two Q buckets (>= ``config.q_bucket``), so the set of
    compiled signatures is bounded by O(log max_Q) x ops, not by traffic.

    Tenants idle for ``config.idle_ttl`` consecutive steps are evicted
    (lane zeroed and returned to the free list — ``fleet.idle_evictions``
    counts them); a later ``connect`` under the same id starts fresh.

    Posterior std queries (``op='query'`` with ``payload=(Xq, True)``)
    need the per-tenant variance ``GramSolver`` — an O(cap^4) LU that does
    not batch across tenants — so they are served per tenant through an
    LRU keyed on ``(slot, factor_revision, noise, signal)``: extend/refit
    bump the tenant's factor revision and miss; resolve() and pure queries
    keep it and hit (same contract as ``GPServeBundle.refresh_solver``).
    """

    def __init__(self, fleet=None, *, kernel="rbf", d=None, config=None,
                 injector=None, journal=None, **fleet_kwargs):
        import collections

        from repro.configs.paper_gp import GP_FLEET
        from repro.core.fleet import GPFleet

        self.config = config or GP_FLEET
        if fleet is None:
            fleet = GPFleet(kernel, d=d, batch=self.config.batch,
                            window=self.config.window, **fleet_kwargs)
        self.fleet = fleet
        self._queue: collections.deque = collections.deque()
        # adopt tenants already joined on a caller-supplied fleet
        self._idle: dict = {t: 0 for t in fleet.tenants}
        self._solvers: Any = collections.OrderedDict()
        self.steps = 0
        # -- resilience wiring (DESIGN.md sec. 17.3) ----------------------
        self.injector = injector          # ChaosInjector (drills/tests)
        self.journal = journal            # resilience.Journal (recovery)
        self._failures: dict = {}         # tenant -> consecutive faults
        self._quarantined: set = set()
        if _obs.enabled():
            for name in ("fleet.serve.requests", "fleet.serve.steps",
                         "fleet.idle_evictions",
                         "fleet.solver_cache.hits",
                         "fleet.solver_cache.misses",
                         "resilience.load_shed",
                         "resilience.deadline_expired",
                         "resilience.retries"):
                _obs.REGISTRY.inc(name, 0)

    # -- tenant lifecycle --------------------------------------------------

    def connect(self, tenant, **hypers) -> None:
        if tenant in self._quarantined:
            raise TenantQuarantinedError(
                f"tenant {tenant!r} is quarantined")
        self.fleet.join(tenant, **hypers)
        self._idle[tenant] = 0
        self._failures.pop(tenant, None)
        if self.journal is not None:
            self.journal.record("join", tenant=tenant,
                                args={k: float(v)
                                      for k, v in hypers.items()})

    def disconnect(self, tenant) -> None:
        self._queue = type(self._queue)(
            r for r in self._queue if r.tenant != tenant)
        self._idle.pop(tenant, None)
        slot = self.fleet.slot_of(tenant)
        self._solvers = type(self._solvers)(
            (k, v) for k, v in self._solvers.items() if k[0] != slot)
        self.fleet.leave(tenant)
        if self.journal is not None:
            self.journal.record("leave", tenant=tenant)

    @property
    def tenants(self):
        return self.fleet.tenants

    # -- request intake ----------------------------------------------------

    def submit(self, tenant, op: str, payload=None) -> FleetRequest:
        """Enqueue an op; returns the request (poll ``.done``/``.result``
        after ``step``/``drain``).

        Admission is where resilience bites first: quarantined tenants are
        refused, a full queue sheds with a typed ``ShedResponse`` result,
        and non-finite extend payloads are rejected BEFORE they can touch
        a factor strip (the request completes with the typed error as its
        result — repeated offenders get quarantined)."""
        if tenant in self._quarantined:
            raise TenantQuarantinedError(f"tenant {tenant!r} is quarantined")
        if tenant not in self._idle:
            raise KeyError(f"tenant {tenant!r} is not connected")
        if op not in ("extend", "evict", "resolve", "refit", "query"):
            raise ValueError(f"unknown fleet op {op!r}")
        req = FleetRequest(tenant=tenant, op=op, payload=payload,
                           deadline=self.steps + self.config.deadline_steps)
        if _obs.enabled():
            _obs.REGISTRY.inc("fleet.serve.requests")
        # load shedding: a bounded queue is the backpressure contract —
        # the caller gets a typed shed value immediately, never a stall
        if len(self._queue) >= self.config.max_queue:
            req.done = True
            req.result = ShedResponse(reason="queue_full",
                                      queue_depth=len(self._queue))
            if _obs.enabled():
                _obs.REGISTRY.inc("resilience.load_shed")
            _obs.emit({"type": "load_shed", "tenant": str(tenant)})
            return req
        # chaos: corrupt an extend payload on a nan_payload draw (the
        # admission guardrail below must catch it)
        if op == "extend" and payload is not None \
                and self._draw("nan_payload"):
            x, g = payload
            req.payload = payload = (self.injector.corrupt_payload(x), g)
            req.chaos_kind = "nan_payload"
        # chaos: stragglers park past their own deadline — the sweep in
        # step() must expire them without stalling anyone else
        if op == "query" and self._draw("straggler"):
            req.chaos_kind = "straggler"
            req.not_before = req.deadline + 1
        if op == "extend" and payload is not None:
            try:
                x, g = payload
                _guard.check_finite(x, g, what="observation", tenant=tenant)
            except NonFiniteObservationError as e:
                req.done = True
                req.result = e
                if req.chaos_kind == "nan_payload":
                    _guard.record_recovery("nan_payload",
                                           tenant=str(tenant))
                self._note_failure(tenant)
                return req
        self._queue.append(req)
        return req

    def _draw(self, kind: str) -> bool:
        """One injector Bernoulli draw (False without a ChaosInjector)."""
        draw = getattr(self.injector, "draw", None)
        return bool(draw is not None and draw(kind))

    def _note_failure(self, tenant) -> None:
        """Count a tenant-attributed fault; quarantine past the threshold
        (mask flip via ``GPFleet.quarantine`` — no repack, no recompile)."""
        self._failures[tenant] = self._failures.get(tenant, 0) + 1
        if self._failures[tenant] < self.config.quarantine_threshold:
            return
        self._quarantined.add(tenant)
        self._failures.pop(tenant, None)
        self._idle.pop(tenant, None)
        slot = self.fleet.slot_of(tenant)
        self._solvers = type(self._solvers)(
            (k, v) for k, v in self._solvers.items() if k[0] != slot)
        # pending requests fail typed — the queue never wedges on a
        # quarantined tenant
        kept = type(self._queue)()
        for r in self._queue:
            if r.tenant == tenant:
                r.done = True
                r.result = TenantQuarantinedError(
                    f"tenant {tenant!r} quarantined while queued")
            else:
                kept.append(r)
        self._queue = kept
        self.fleet.quarantine(tenant)
        if self.journal is not None:
            self.journal.record("leave", tenant=tenant)

    # -- the packing loop --------------------------------------------------

    def _take_head_of_line(self) -> list:
        """Pop at most one pending request per tenant, FIFO order — a
        tenant's ops are never reordered and never co-batched within one
        step (extend-then-query in one step would race)."""
        taken, skipped, busy = [], [], set()
        while self._queue:
            r = self._queue.popleft()
            if r.not_before > self.steps:
                # backoff/straggler parking: not eligible yet, but it
                # still holds its tenant's head-of-line slot (order!)
                busy.add(r.tenant)
                skipped.append(r)
            elif r.tenant in busy:
                skipped.append(r)
            else:
                busy.add(r.tenant)
                taken.append(r)
        self._queue.extend(skipped)
        return taken

    def step(self) -> list:
        """Pack + launch one round; returns the completed requests.

        Hardened path: expired requests are swept out first (typed
        ``DeadlineExceededError``), then each per-op group launches under
        the bounded-retry protocol — an injected kill requeues the group
        with exponential step backoff until ``config.max_retries`` is
        spent, after which requests complete with ``RetryExhaustedError``.
        A request never blocks forever and a fault in one op group never
        poisons the others."""
        from repro.runtime.recovery import SimulatedFailure

        cfg = self.config
        with _obs.span("fleet.serve.step", queued=len(self._queue)):
            self.steps += 1
            completed = self._sweep_deadlines()
            with _obs.span("fleet.pack"):
                by_op: dict = {}
                for r in self._take_head_of_line():
                    by_op.setdefault(r.op, []).append(r)
            # lifecycle before queries: a step's queries see that step's
            # extends only for OTHER tenants (self ops are serialized by
            # head-of-line), so order here is launch-count, not semantics
            for op in ("extend", "evict", "resolve", "refit", "query"):
                reqs = by_op.get(op)
                if not reqs:
                    continue
                try:
                    kill = getattr(self.injector, "maybe_kill", None)
                    if kill is not None:
                        kill()
                    self._launch_group(op, reqs)
                except SimulatedFailure:
                    _guard.record_recovery("kill_step", op=op)
                    completed.extend(self._requeue(reqs))
                    continue
                for r in reqs:
                    r.done = True
                completed.extend(reqs)
            # idle bookkeeping + TTL eviction
            active = {r.tenant for r in completed}
            for t in list(self._idle):
                self._idle[t] = 0 if t in active else self._idle[t] + 1
                if self._idle[t] > cfg.idle_ttl:
                    self.disconnect(t)
                    if _obs.enabled():
                        _obs.REGISTRY.inc("fleet.idle_evictions")
            if _obs.enabled():
                _obs.REGISTRY.inc("fleet.serve.steps")
                _obs.REGISTRY.set_gauge("fleet.serve.queue_depth",
                                        len(self._queue))
        return completed

    def _launch_group(self, op: str, reqs: list) -> None:
        """One vmapped launch for an op group (+ journal on success)."""
        cfg, fl = self.config, self.fleet
        if op == "extend":
            fl.extend({r.tenant: r.payload for r in reqs})
            if self.journal is not None:
                self.journal.record_fleet("extend", per_tenant={
                    r.tenant: {"x": r.payload[0], "g": r.payload[1]}
                    for r in reqs})
        elif op == "evict":
            fl.evict([r.tenant for r in reqs])
            if self.journal is not None:
                self.journal.record("evict",
                                    tenants=[r.tenant for r in reqs])
        elif op == "resolve":
            fl.resolve({r.tenant: r.payload for r in reqs})
            if self.journal is not None:
                self.journal.record_fleet("resolve", per_tenant={
                    r.tenant: {"rhs": r.payload} for r in reqs})
        elif op == "refit":
            mlls = fl.refit([r.tenant for r in reqs],
                            steps=cfg.refit_steps, lr=cfg.refit_lr)
            for r in reqs:
                r.result = mlls.get(r.tenant)
            if self.journal is not None:
                self.journal.record("refit",
                                    tenants=[r.tenant for r in reqs],
                                    args={"steps": cfg.refit_steps,
                                          "lr": cfg.refit_lr})
        elif op == "query":
            self._serve_queries(reqs)

    def _requeue(self, reqs: list) -> list:
        """Bounded retry: requeue a killed group with exponential step
        backoff; past the budget, complete with RetryExhaustedError.
        Returns the requests that just failed terminally."""
        failed = []
        for r in reversed(reqs):            # appendleft: keep FIFO order
            r.attempts += 1
            if r.attempts > self.config.max_retries:
                r.done = True
                r.result = RetryExhaustedError(
                    f"{r.op!r} for tenant {r.tenant!r} failed "
                    f"{r.attempts} times")
                if _obs.enabled():
                    _obs.REGISTRY.inc("resilience.retry_exhausted")
                failed.append(r)
                continue
            r.not_before = self.steps + 2 ** r.attempts
            if _obs.enabled():
                _obs.REGISTRY.inc("resilience.retries")
            self._queue.appendleft(r)
        return failed

    def _sweep_deadlines(self) -> list:
        """Expire queued requests whose deadline has passed (typed result,
        never a silent drop); chaos-parked stragglers count as recovered
        the moment the sweep catches them."""
        expired, kept = [], type(self._queue)()
        for r in self._queue:
            if r.deadline is not None and self.steps > r.deadline:
                r.done = True
                r.result = DeadlineExceededError(
                    f"{r.op!r} for tenant {r.tenant!r} expired at "
                    f"step {self.steps} (deadline {r.deadline})")
                if _obs.enabled():
                    _obs.REGISTRY.inc("resilience.deadline_expired")
                if r.chaos_kind == "straggler":
                    _guard.record_recovery("straggler",
                                           tenant=str(r.tenant))
                expired.append(r)
            else:
                kept.append(r)
        self._queue = kept
        return expired

    def drain(self, max_steps: int = 10_000) -> int:
        """Step until the queue is empty; returns the number of steps."""
        n = 0
        while self._queue and n < max_steps:
            self.step()
            n += 1
        return n

    # -- queries -----------------------------------------------------------

    def _serve_queries(self, reqs: list) -> None:
        mean_reqs, std_reqs = [], []
        for r in reqs:
            xq, want_std = (r.payload if isinstance(r.payload, tuple)
                            else (r.payload, False))
            (std_reqs if want_std else mean_reqs).append((r, xq))
        if mean_reqs:
            with _obs.span("fleet.pack"):
                qmax = max(jnp.atleast_2d(jnp.asarray(x)).shape[0]
                           for _, x in mean_reqs)
                bucket = max(self.config.q_bucket,
                             1 << (max(qmax, 1) - 1).bit_length())
            out = self.fleet.posterior(
                {r.tenant: x for r, x in mean_reqs}, q_pad=bucket)
            with _obs.span("fleet.unpack"):
                for r, _ in mean_reqs:
                    r.result = out[r.tenant]
        for r, xq in std_reqs:
            r.result = self.query_std(r.tenant, xq)

    def query_std(self, tenant, Xq):
        """Per-tenant posterior mean + std (the non-batched slow path).

        Served from the tenant's lane view through the factor-revision
        solver LRU; like the PR 7 sharded path, variance queries are NOT
        fleet-batched (the GramSolver is a per-tenant O(cap^4) LU with no
        batched factorization yet — see DESIGN.md sec. 15).
        """
        from repro.core.gram import GramFactors
        from repro.core.query import make_query_fn
        from repro.hyper.variance import make_solver

        fl = self.fleet
        slot = fl.slot_of(tenant)
        lane = fl.state_view(tenant)
        hyp = fl.hypers_of(tenant)
        key = (slot, fl.factor_revision[slot], hyp["noise"], hyp["signal"])
        solver = self._solvers.get(key)
        if solver is None:
            if _obs.enabled():
                _obs.REGISTRY.inc("fleet.solver_cache.misses")
            f = GramFactors(K1e=lane.K1e, K2e=lane.K2e, Xt=lane.Xt,
                            lam=lane.lam, noise=0.0, c=None)
            solver = make_solver(fl.spec, f, noise=hyp["noise"],
                                 signal=hyp["signal"], count=lane.count)
            self._solvers[key] = solver
            while len(self._solvers) > self.config.solver_cache_max:
                self._solvers.popitem(last=False)
        else:
            self._solvers.move_to_end(key)
            if _obs.enabled():
                _obs.REGISTRY.inc("fleet.solver_cache.hits")
        f = GramFactors(K1e=lane.K1e, K2e=lane.K2e, Xt=lane.Xt,
                        lam=lane.lam, noise=0.0, c=None)
        qfn = _cw.wrap(make_query_fn(fl.spec, with_std=True),
                       name="fleet_query_std") if not hasattr(
                           self, "_std_step") else self._std_step
        self._std_step = qfn
        Xq = jnp.atleast_2d(jnp.asarray(Xq, lane.X.dtype))
        q = Xq.shape[0]
        bucket = max(self.config.q_bucket, 1 << (max(q, 1) - 1).bit_length())
        Xp = jnp.pad(Xq, ((0, bucket - q), (0, 0)))
        out = qfn(f, lane.Z, solver, Xp)
        from repro.core.query import PosteriorBatch

        return PosteriorBatch(value=out.value[:q], grad=out.grad[:q],
                              std=out.std[:q])


# ---------------------------------------------------------------------------
# D-sharded GP posterior serving (core/dist_state.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedGPServeBundle:
    """Batched mean-query endpoint over a live ``ShardedGPGState``.

    Each microbatch is ONE fused psum of O(Q N) bytes (independent of D
    and of device count — DESIGN.md sec. 14); the compiled shard_map
    program is cached on the state per (microbatch, chunks) and survives
    extend/evict/refit (count and noise are traced arguments).  Mean-only:
    probe/std queries need the (N, D)-resident variance solver and stay on
    the single-device ``GPGState`` path.
    """

    state: Any                       # ShardedGPGState
    microbatch: int
    chunks: Optional[int] = None     # ring-pipelined query path when set

    def query(self, Xq):
        from repro.core.query import PosteriorBatch

        st = self.state
        Xq = jnp.atleast_2d(Xq)
        q = Xq.shape[0]
        b = self.microbatch
        pad = (-q) % b
        Xp = jnp.pad(jnp.asarray(Xq, jnp.asarray(st.data.base.X).dtype),
                     ((0, pad), (0, 0)))
        with _obs.span("serve.query.sharded", q=q, shards=st.ndev):
            if _obs.enabled():
                _obs.REGISTRY.inc("serve.requests")
                _obs.REGISTRY.inc("serve.points", q)
                _obs.REGISTRY.set_gauge("serve.queue_depth", (q + pad) // b)
            outs = [st.posterior(Xp[i:i + b], chunks=self.chunks)
                    for i in range(0, q + pad, b)]
        return PosteriorBatch(
            value=jnp.concatenate([o.value for o in outs])[:q],
            grad=jnp.concatenate([o.grad for o in outs])[:q],
        )


def build_sharded_gp_serve_step(state, *, microbatch: int | None = None,
                                chunks: int | None = None,
                                config=None) -> ShardedGPServeBundle:
    """Compile a batched mean-query step for a ``ShardedGPGState``.

    The D-sharded analogue of :func:`build_gp_serve_step`: requests are
    padded to ``microbatch`` multiples and each chunk runs the state's
    cached shard_map query program (one fused psum of the (Q, N) cross
    strips per chunk).  ``chunks`` switches to the ring-pipelined
    (ppermute) variant, overlapping each sub-chunk's reduction with the
    next one's local factor sweep — flat one-axis meshes only.
    """
    from repro.configs.paper_gp import GP_SERVE
    from repro.core.dist_state import ShardedGPGState

    if not isinstance(state, ShardedGPGState):
        raise TypeError("build_sharded_gp_serve_step needs a "
                        "ShardedGPGState (build_gp_serve_step serves the "
                        "single-device GPGState)")
    if microbatch is None:
        microbatch = (config or GP_SERVE).microbatch
    if _obs.enabled():
        for name in ("serve.requests",):
            _obs.REGISTRY.inc(name, 0)
    return ShardedGPServeBundle(state=state, microbatch=int(microbatch),
                                chunks=chunks)
