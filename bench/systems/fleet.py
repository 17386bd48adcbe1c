"""Many tenants of one ``GPFleetServer``, each a closed-loop client.

Configuration keys: ``kernel``, ``d``, ``window``, ``tenants``, ``lanes``
(``GPFleetConfig.batch``), ``noise_frac``, ``signal``.  Traffic keys:
``cycle`` (each tenant's requests, repeated; tenant t starts at position
t of the cycle, so the op groups of a server step keep their sizes),
``q`` and ``spread`` (a query's points around the tenant's newest input),
``obs_pool`` (distinct observations per tenant, streamed in turn; more
than ``window``, so a window never holds one twice), ``warm_cycles``
and ``check_samples`` (query answers kept, drawn from the seed, for the
reference).

Each tenant observes its own function (``gen.Problem`` of the tenant).
Its observations, and for each of them a query batch around it, are made
on the device in set-up and kept on the host, as a client holds its
payloads.  A tenant submits its next request when the previous one has
completed (zero think time); ``server.step()`` runs until the window
ends.  A request's latency runs from ``submit`` to the end of the
``step()`` that completed it, with a query's answer on the host side of
``block_until_ready``.
"""
from __future__ import annotations

import collections
import random
import time

import jax
import numpy as np

import gen


class System:
    SPANS = ("client", "server.step")

    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        from repro.configs.paper_gp import GPFleetConfig
        from repro.train.serve import GPFleetServer

        self.traffic = traffic
        self.d, self.window = int(cfg["d"]), int(cfg["window"])
        self.q, self.spread = int(traffic["q"]), float(traffic["spread"])
        self.pool = int(traffic["obs_pool"])
        if self.pool <= self.window:
            raise ValueError("obs_pool must exceed the window")
        self.cycle = list(traffic["cycle"])
        self.hp = gen.hypers(cfg)
        self.seed = seed
        self.rng = random.Random(seed)
        self.server = GPFleetServer(
            kernel=cfg["kernel"], d=self.d,
            config=GPFleetConfig(batch=int(cfg["lanes"]),
                                 window=self.window))
        self.tenants = list(range(int(cfg["tenants"])))
        for t in self.tenants:
            self.server.connect(t, lam=self.hp["lam"],
                                noise=self.hp["noise"],
                                signal=self.hp["signal"])
        self.obs: dict = {}     # tenant -> (X (pool, d), G (pool, d))
        self.qs: dict = {}      # tenant -> (pool, q, d) around each input
        self.n_obs = collections.Counter()
        self.n_req = collections.Counter()
        self.kept: list = []
        self.seen = 0

    def _payloads(self, ann) -> None:
        with ann("client"):
            for t in self.tenants:
                p = gen.Problem(self.seed, self.d, tenant=t)
                xs, gs, qs = [], [], []
                for i in range(self.pool):
                    x, g = p.observation(i)
                    xs.append(x)
                    gs.append(g)
                    qs.append(p.queries(i, x, self.q, self.spread))
                self.obs[t] = jax.device_get((jax.numpy.stack(xs),
                                              jax.numpy.stack(gs)))
                self.qs[t] = jax.device_get(jax.numpy.stack(qs))

    # -- traffic -----------------------------------------------------------

    def _submit(self, t):
        op = self.cycle[(t + self.n_req[t]) % len(self.cycle)]
        self.n_req[t] += 1
        if op == "extend":
            i = self.n_obs[t] % self.pool
            self.n_obs[t] += 1
            payload = (self.obs[t][0][i], self.obs[t][1][i])
            meta = None
        elif op == "query":
            newest = self.n_obs[t] - 1
            payload = self.qs[t][newest % self.pool]
            meta = {"tenant": t, "around": newest,
                    "window": (self.n_obs[t] - self.window, self.n_obs[t])}
        else:
            raise ValueError(f"unknown request {op!r} in the traffic")
        return self.server.submit(t, op, payload), meta, time.perf_counter()

    def _keep(self, meta, pb) -> None:
        k = int(self.traffic["check_samples"])
        self.seen += 1
        slot = len(self.kept) if len(self.kept) < k \
            else self.rng.randrange(self.seen)
        if slot < k:
            item = (meta, (pb.value, pb.grad))
            if slot == len(self.kept):
                self.kept.append(item)
            else:
                self.kept[slot] = item

    def loop(self, ann, seconds: float, keep: bool) -> dict:
        """Every tenant in flight; server steps until ``seconds`` pass."""
        lat, ops, failed, steps = [], collections.Counter(), 0, 0
        live = {t: self._submit(t) for t in self.tenants}
        t0 = time.perf_counter()
        while True:
            with ann("server.step"):
                done = self.server.step()
                jax.block_until_ready([r.result.grad for r in done
                                       if hasattr(r.result, "grad")])
            now = time.perf_counter()
            steps += 1
            for r in done:
                req, meta, sent = live[r.tenant]
                if isinstance(r.result, Exception) or (
                        r.op == "query" and not hasattr(r.result, "grad")):
                    failed += 1
                else:
                    ops[r.op] += 1
                    lat.append(now - sent)
                    if keep and meta is not None:
                        self._keep(meta, r.result)
            if now - t0 >= seconds:
                break
            for r in done:
                live[r.tenant] = self._submit(r.tenant)
        # what is still queued is not in the window's count
        self.server.drain()
        return {"steps": steps, "ops": dict(ops), "latencies_s": lat,
                "completed": sum(ops.values()),
                "attempted": sum(ops.values()) + failed, "failed": failed}

    def setup(self, ann) -> None:
        self._payloads(ann)
        for _ in range(self.window):
            for t in self.tenants:
                i = self.n_obs[t] % self.pool
                self.n_obs[t] += 1
                self.server.submit(t, "extend",
                                   (self.obs[t][0][i], self.obs[t][1][i]))
            self.server.step()
        for _ in range(int(self.traffic["warm_cycles"])):
            self.loop(ann, 0.0, False)

    def run(self, seconds: float, ann) -> dict:
        return self.loop(ann, seconds, True)

    # -- after the window --------------------------------------------------

    def shapes(self) -> dict:
        item = np.dtype(np.float32).itemsize
        n = self.window
        return {"extend": {"n": n, "d": self.d, "itemsize": item},
                "query": {"n": n, "d": self.d, "q": self.q,
                          "itemsize": item}}

    def samples(self) -> list:
        """The kept answers with the inputs rebuilt from the seed."""
        out = []
        for meta, (value, grad) in self.kept:
            p = gen.Problem(self.seed, self.d, tenant=meta["tenant"])
            lo, hi = meta["window"]
            obs = [jax.device_get(p.observation(i % self.pool))
                   for i in range(lo, hi)]
            i = meta["around"] % self.pool
            Xq = p.queries(i, p.observation(i)[0], self.q, self.spread)
            out.append({"X": np.stack([x for x, _ in obs]),
                        "G": np.stack([g for _, g in obs]),
                        "Xq": jax.device_get(Xq),
                        "lam": self.hp["lam"],
                        "noise": self.hp["noise"] / self.hp["signal"],
                        "value": jax.device_get(value),
                        "grad": jax.device_get(grad)})
        return out

    def free(self) -> None:
        self.kept.clear()
        self.obs.clear()
        self.qs.clear()
        self.server = None
