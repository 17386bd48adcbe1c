"""One client streaming into one ``GPGState`` and querying it.

Configuration keys: ``kernel``, ``d``, ``window``, ``microbatch``,
``noise_frac``, ``signal``.  Traffic keys: ``cycle`` (the client's
requests, repeated: "extend" streams the next observation in, "query"
asks for ``q`` points ``spread`` around an input of the window),
``warm_cycles`` (cycles run in set-up after the window is filled, the
last of them keeping its answers as the window does),
``check_samples`` (query answers kept, drawn from the seed, for the
reference) and ``check_points`` (rows of each kept answer, drawn from the
seed, that the reference recomputes).

A query is made on the device by the client before it is sent, around the
newest input where the cycle extends, and around the window's inputs in
turn where it does not.  The window is filled in set-up, so every extend
in the window evicts.  The loop is closed: each request is sent when the
previous one has come back (``block_until_ready`` of its outputs).
"""
from __future__ import annotations

import collections
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen


class System:
    SPANS = ("client", "extend", "query")

    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        from repro.core import GPGState
        from repro.train.serve import build_gp_serve_step

        self.traffic = traffic
        self.d, self.window = int(cfg["d"]), int(cfg["window"])
        self.q, self.spread = int(traffic["q"]), float(traffic["spread"])
        self.hp = gen.hypers(cfg)
        self.problem = gen.Problem(seed, self.d)
        self.rng = random.Random(seed)
        self.n_obs = 0          # observations streamed in so far
        self.n_q = 0            # query batches served so far
        self.inputs = collections.deque(maxlen=self.window)
        self.cg_iters = 0
        self.kept: list = []    # reservoir of (query meta, served rows)
        self.seen = 0
        self.state = GPGState(cfg["kernel"], self.d, window=self.window,
                              dtype=jnp.float32, **self.hp)
        self.bundle = build_gp_serve_step(
            self.state, microbatch=int(cfg["microbatch"]))

    # -- traffic -----------------------------------------------------------

    def extend(self, ann) -> None:
        x, g = self.problem.observation(self.n_obs)
        with ann("extend"):
            self.state.extend(x, g)
            jax.block_until_ready(self.state.data.Z)
        self.cg_iters += int(self.state.data.cg_iters)
        self.n_obs += 1
        self.inputs.append(x)

    def query(self, ann, keep: bool) -> float:
        j = self.n_q
        back = 0 if "extend" in self.traffic["cycle"] else j % self.window
        around = self.n_obs - 1 - back
        with ann("client"):
            Xq = jax.block_until_ready(self.problem.queries(
                j, self.inputs[-1 - back], self.q, self.spread))
        t0 = time.perf_counter()
        with ann("query"):
            out = self.bundle.query(Xq)
            jax.block_until_ready((out.value, out.grad))
        dt = time.perf_counter() - t0
        self.n_q += 1
        if keep:
            self._keep({"j": j, "around": around,
                        "window": (self.n_obs - self.window, self.n_obs)},
                       out)
        return dt

    def _keep(self, meta, out) -> None:
        """Reservoir sample of the window's answers, and of each answer's
        rows, drawn from the seed."""
        k = int(self.traffic["check_samples"])
        self.seen += 1
        slot = len(self.kept) if len(self.kept) < k \
            else self.rng.randrange(self.seen)
        if slot >= k:
            return
        rows = sorted(self.rng.sample(range(self.q),
                                      int(self.traffic["check_points"])))
        idx = jnp.asarray(rows)
        item = (dict(meta, rows=rows), (out.value[idx], out.grad[idx]))
        if slot == len(self.kept):
            self.kept.append(item)
        else:
            self.kept[slot] = item

    def step(self, ann, keep: bool, ops, latencies) -> None:
        for op in self.traffic["cycle"]:
            if op == "extend":
                self.extend(ann)
            elif op == "query":
                latencies.append(self.query(ann, keep))
            else:
                raise ValueError(f"unknown request {op!r} in the traffic")
            ops[op] += 1

    def setup(self, ann) -> None:
        for _ in range(self.window):
            self.extend(ann)
        warm = int(self.traffic["warm_cycles"])
        for i in range(warm):
            # the last cycle keeps its answers, so that the slicing of a
            # kept answer is built here and not in the window
            self.step(ann, i == warm - 1, collections.Counter(), [])
        self.kept.clear()
        self.seen = 0
        self.cg_iters = 0

    def run(self, seconds: float, ann) -> dict:
        ops, lat, steps = collections.Counter(), [], 0
        t0 = time.perf_counter()
        while True:
            self.step(ann, True, ops, lat)
            steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
        n = sum(ops.values())
        return {"steps": steps, "ops": dict(ops), "latencies_s": lat,
                "completed": n, "attempted": n, "failed": 0,
                "cg_iters": self.cg_iters if ops.get("extend") else None}

    # -- after the window --------------------------------------------------

    def shapes(self) -> dict:
        item = jnp.dtype(jnp.float32).itemsize
        n = self.window
        return {"extend": {"n": n, "d": self.d, "itemsize": item},
                "query": {"n": n, "d": self.d, "q": self.q,
                          "itemsize": item}}

    def samples(self) -> list:
        """The kept rows with the inputs rebuilt from the seed."""
        out = []
        for meta, (value, grad) in self.kept:
            lo, hi = meta["window"]
            obs = [jax.device_get(self.problem.observation(i))
                   for i in range(lo, hi)]
            around = self.problem.observation(meta["around"])[0]
            Xq = self.problem.queries(meta["j"], around, self.q, self.spread)
            out.append({"X": np.stack([x for x, _ in obs]),
                        "G": np.stack([g for _, g in obs]),
                        "Xq": jax.device_get(Xq[jnp.asarray(meta["rows"])]),
                        "lam": self.hp["lam"],
                        "noise": self.hp["noise"] / self.hp["signal"],
                        "value": jax.device_get(value),
                        "grad": jax.device_get(grad)})
            del Xq
        return out

    def free(self) -> None:
        self.kept.clear()
        self.inputs.clear()
        self.state = self.bundle = None
