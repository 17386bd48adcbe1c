"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
structure, and everything else works on that structure alone, so the
reduction is tested on a recorded event list without a chip:

    {"t0": ns, "t1": ns,                       # the benchmark's "window" span
     "devices": [[(op, start_ns, dur_ns), ...], ...],   # one list per chip
     "spans": [(name, start_ns, dur_ns), ...]}  # the benchmark's annotations

Device time is the union of the intervals in which an operation ran on a
chip; each number below is the mean over the chips.
"""
from __future__ import annotations

import collections

WINDOW = "window"


def load(path: str, span_names) -> dict:
    """Device ops ("XLA Ops" lines of the TPU planes) and the benchmark's
    host annotations, on the profiler's one clock."""
    from jax.profiler import ProfileData

    wanted = set(span_names) | {WINDOW}
    devices, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            devices.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name in wanted]
    devices.sort(key=lambda p: int(p[0].rsplit(":", 1)[1]))
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"the trace holds {len(windows)} window spans")
    _, t0, dur = windows[0]
    return {"t0": t0, "t1": t0 + dur, "devices": [ops for _, ops in devices],
            "spans": [s for s in spans if s[0] != WINDOW]}


def op_name(hlo: str) -> str:
    """The op's name without its HLO text and numeric suffix:
    "%fused_gram_mvm_padded.3 = f32[...] custom-call(...)" ->
    "fused_gram_mvm_padded"."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def union(ops, lo: int, hi: int) -> list:
    """Sorted disjoint (start, end) intervals covered by ``ops``, clipped
    to [lo, hi]."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in ops
                if s < hi and s + d > lo)
    out: list = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def overlap(intervals, lo: int, hi: int) -> int:
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in intervals)


def window_s(tr: dict) -> float:
    return (tr["t1"] - tr["t0"]) * 1e-9


def busy_s(tr: dict) -> float:
    """Seconds in the window in which some operation ran, mean over chips."""
    per = [overlap(union(ops, tr["t0"], tr["t1"]), tr["t0"], tr["t1"])
           for ops in tr["devices"]]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def spans(tr: dict, name: str) -> list:
    return [(s, s + d) for n, s, d in tr["spans"] if n == name]


def busy_in(tr: dict, name: str) -> list:
    """Per span named ``name``: (span seconds, device-busy seconds inside
    it, mean over chips)."""
    unions = [union(ops, tr["t0"], tr["t1"]) for ops in tr["devices"]]
    out = []
    for lo, hi in spans(tr, name):
        busy = sum(overlap(u, lo, hi) for u in unions) / max(len(unions), 1)
        out.append(((hi - lo) * 1e-9, busy * 1e-9))
    return out


def top_ops(tr: dict, k: int = 10) -> list:
    """[[op, seconds]] of the k ops that took most device time, by self
    time (mean over chips)."""
    lo, hi = tr["t0"], tr["t1"]
    tot: dict = collections.Counter()
    for ops in tr["devices"]:
        for n, s, d in self_times(ops):
            tot[n] += max(0, min(s + d, hi) - max(s, lo))
    nd = max(len(tr["devices"]), 1)
    return [[n, t / nd * 1e-9] for n, t in tot.most_common(k) if t > 0]


def self_times(ops) -> list:
    """(op, start, self ns): an op that encloses others (a while loop and
    its body) keeps only the time none of them covers, so that no device
    time is counted twice."""
    out, stack = [], []       # stack: [name, start, end, covered by children]
    for n, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[1], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(s + d, stack[-1][2]) - s
        stack.append([n, s, s + d, 0])
    out += [(n, s, e - s - c) for n, s, e, c in stack]
    return out


def idle_by_span(tr: dict, k: int = 10) -> list:
    """[[annotation, seconds]]: the device's idle time in the window, split
    by the benchmark annotation open on the host at the time ("none" where
    none was), mean over chips, largest first."""
    lo, hi = tr["t0"], tr["t1"]
    named = sorted((s, s + d, n) for n, s, d in tr["spans"])
    tot: dict = collections.Counter()
    for ops in tr["devices"]:
        edges = [lo] + [t for iv in union(ops, lo, hi) for t in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            covered = 0
            for s, e, n in named:
                part = max(0, min(e, ge) - max(s, gs))
                if part:
                    tot[n] += part
                    covered += part
            tot["none"] += max(0, (ge - gs) - covered)
    nd = max(len(tr["devices"]), 1)
    return [[n, t / nd * 1e-9] for n, t in tot.most_common(k) if t > 0]
