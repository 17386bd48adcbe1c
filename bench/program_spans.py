"""The program's own spans beside the device trace: where the host's time
goes inside the program's calls.

The program (``repro.obs.trace``) keeps the spans it ran, and the
executables it built, while a profiler session was active:

    {"spans": [(name, start_ns, end_ns, depth), ...],
     "compiles": [(innermost span, end_ns, seconds), ...]}

on the host's wall clock.  The trace that ``tracing.load`` reads is on
the profiler's clock, which counts from the session's start.  ``load``
puts the program's spans on the trace's clock by the benchmark's own
annotations: each of the program's outermost spans runs inside the
benchmark annotation around the call that made it (``offset``).  A program that
keeps no such record (one older than its spans) gives None, and so do
the metrics that read it.
"""
from __future__ import annotations

import bisect
import statistics

import tracing

PREFIXES = ("state.", "guard.", "serve.", "fleet.")
CANDIDATES = 16     # benchmark annotations tried for the first program span


def recorded(rec: dict):
    """The program's record of the run: ``rec["program"]`` where the caller
    gives one, else what the program kept; None without either."""
    if "program" in rec:
        return rec["program"]
    try:
        from repro.obs import trace as program_trace
    except ImportError:
        return None
    profiled = getattr(program_trace, "profiled", None)
    return profiled() if profiled is not None else None


def load(rec: dict):
    """``{"spans": [(name, start, dur, depth)], "compiles": [...]}`` with
    the spans of the program's prefixes on the trace's clock, or None."""
    tr, prog = rec.get("trace"), recorded(rec)
    if not tr or prog is None:
        return None
    spans = [s for s in prog["spans"] if s[0].startswith(PREFIXES)]
    off = offset(spans, tr["spans"])
    if off is None:
        return None
    return {"spans": [(n, s + off, e - s, d) for n, s, e, d in spans],
            "compiles": list(prog["compiles"])}


def offset(spans, bench_spans):
    """Trace clock minus wall clock, or None without outermost program
    spans or annotations.  Each candidate shift puts the first outermost
    span at the start of one of the first annotations; the one that puts
    the midpoints of most outermost spans inside annotations pairs them
    (midpoints, since the two clocks' readings of one call differ by
    microseconds), and of the shifts that keep every pair nested the
    smallest is taken."""
    top = sorted((s, e) for _, s, e, d in spans if d == 0)
    bench = sorted((s, s + d) for _, s, d in bench_spans)
    if not top or not bench:
        return None
    starts = [s for s, _ in bench]

    def pairs(off):
        out = []
        for s, e in top:
            mid = (s + e) // 2 + off
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < bench[i][1]:
                out.append(((s, e), bench[i]))
        return out

    best = max((pairs(b0 - top[0][0]) for b0, _ in bench[:CANDIDATES]),
               key=len)
    if not best:
        return None
    lo = max(b[0] - p[0] for p, b in best)
    hi = min(b[1] - p[1] for p, b in best)
    if lo <= hi:
        return lo
    return statistics.median(b[0] - p[0] for p, b in best)


def intervals(prog: dict, names) -> list:
    """The union of the named program spans, as sorted disjoint
    (start, end) pairs."""
    return tracing.union([(n, s, d) for n, s, d, _ in prog["spans"]
                          if n in names], -(1 << 62), 1 << 62)


def covered(ivs, ends, lo: int, hi: int) -> int:
    """ns of [lo, hi] inside the sorted disjoint ``ivs`` (``ends`` their
    ends), by bisection."""
    out = 0
    for s, e in ivs[bisect.bisect_right(ends, lo):]:
        if s >= hi:
            break
        out += min(e, hi) - max(s, lo)
    return out


def idle_in(tr: dict, ivs) -> float:
    """Seconds of the window inside ``ivs`` in which no operation ran on a
    chip, mean over the chips."""
    lo, hi = tr["t0"], tr["t1"]
    ivs = [(max(s, lo), min(e, hi)) for s, e in ivs if s < hi and e > lo]
    total = sum(e - s for s, e in ivs)
    if not tr["devices"]:
        return None
    idle = []
    for ops in tr["devices"]:
        busy = tracing.union(ops, lo, hi)
        idle.append(total - sum(tracing.overlap(busy, s, e)
                                for s, e in ivs))
    return sum(idle) / len(idle) * 1e-9


def idle_ms_per_step(rec: dict, names) -> float | None:
    """Device idle inside the union of the named program spans, in ms per
    step of the window; None where the program recorded none of them."""
    prog = load(rec)
    if prog is None or not rec.get("steps"):
        return None
    ivs = intervals(prog, names)
    if not ivs:
        return None
    idle = idle_in(rec["trace"], ivs)
    return None if idle is None else 1e3 * idle / rec["steps"]


def compile_seconds(rec: dict) -> float | None:
    """Seconds of the executables the program built while it was traced
    (the window), all spans together; None without the program's record."""
    prog = recorded(rec) if rec.get("trace") else None
    if prog is None:
        return None
    return sum(sec for _, _, sec in prog["compiles"])


def idle_by_innermost(rec: dict, within=None, k: int = 20) -> list | None:
    """[[span, seconds]]: the device's idle time in the window by the
    innermost program span open on the host at the time ("none" where
    none was), mean over the chips, largest first.  ``within`` names
    benchmark annotations to look inside (default: the whole window)."""
    prog = load(rec)
    if prog is None or not rec["trace"]["devices"]:
        return None
    tr = rec["trace"]
    lo, hi = tr["t0"], tr["t1"]
    scope = [(lo, hi)] if within is None else tracing.union(
        [s for s in tr["spans"] if s[0] in within], lo, hi)
    scope_ends = [e for _, e in scope]
    busy = [tracing.union(ops, lo, hi) for ops in tr["devices"]]
    busy_ends = [[e for _, e in u] for u in busy]
    # sweep the spans' edges; the open span of greatest depth is the
    # innermost (one thread's spans nest)
    spans = prog["spans"]
    events = sorted([(s, 1, i) for i, (_, s, _, _) in enumerate(spans)]
                    + [(s + d, 0, i) for i, (_, s, d, _) in enumerate(spans)])
    tot: dict = {}
    open_: dict = {}
    prev = lo
    for t, kind, i in events + [(hi, 0, None)]:
        a, b = max(prev, lo), min(t, hi)
        idle = 0
        for s, e in scope[bisect.bisect_right(scope_ends, a):] if b > a \
                else ():
            if s >= b:
                break
            x, y = max(a, s), min(b, e)
            idle += sum(y - x - covered(u, ends, x, y)
                        for u, ends in zip(busy, busy_ends)) / len(busy)
        if idle:
            name = max(open_.values())[1] if open_ else "none"
            tot[name] = tot.get(name, 0) + idle
        prev = max(prev, t)
        if i is None:
            break
        if kind:
            n, _, _, d = spans[i]
            open_[i] = (d, n)
        else:
            open_.pop(i, None)
    return sorted(([n, v * 1e-9] for n, v in tot.items() if v > 0),
                  key=lambda x: -x[1])[:k]
