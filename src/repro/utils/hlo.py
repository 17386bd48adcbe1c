"""HLO-text analysis: collective-communication byte accounting.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but NOT the bytes
moved by collectives; the dry-run therefore parses the compiled HLO text
and sums operand sizes of every collective op (system-prompt roofline
recipe). Parsing is purely lexical — shapes in HLO are printed as e.g.
``bf16[2048,512]{1,0}`` right after the op name.
"""
from __future__ import annotations

import re
from collections import defaultdict

_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "fp8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# e.g. "bf16[256,4096]{1,0}" or "f32[]" — dtype then dims.
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:[a-z0-9]*)?|pred)\[([0-9,]*)\]")

# "%name = <shape or tuple> op-name(" ; tolerate leading spaces and "ROOT".
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s+("
    + "|".join(_COLLECTIVE_OPS)
    + r")(?:-start|-done)?\(",
)


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nbytes
    return total


def collective_breakdown(hlo_text: str) -> dict[str, int]:
    """Map collective op kind -> summed OUTPUT-shape bytes across the module.

    The output shape is what lands on each participating device and is the
    standard proxy for per-device link traffic (an all-gather of a shard to
    a full array writes the full array locally; an all-reduce's result is
    the tensor itself). ``-done`` variants are skipped so async pairs are
    not double counted.
    """
    out: dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        if f"{m.group(2)}-done(" in line:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
    return dict(out)


def collective_bytes(hlo_text: str) -> int:
    """Total collective bytes (sum over all kinds) in an HLO module."""
    return sum(collective_breakdown(hlo_text).values())


_STREAM_CONSUMERS = ("dot_general", "pallas_call")


def _axis_ge(aval, d: int) -> bool:
    shape = getattr(aval, "shape", ())
    return any(isinstance(s, int) and s >= d for s in shape)


def _is_var(v) -> bool:
    from jax.extend.core import Literal

    return not isinstance(v, Literal)


def _walk_streams(jaxpr, tainted: set, d: int, counts: dict) -> set:
    """Taint-propagate a D-axis data argument; classify its consumers.

    A *consumer* is a contraction primitive (``dot_general`` or a
    ``pallas_call`` launch — the only ops that stream an operand through
    the MXU/HBM pipeline); every other eqn just forwards taint to outputs
    that keep a >= d axis (pads/casts/masks/elementwise).  Consumers are
    classified by their outputs: all outputs D-free -> a *reduction*
    stream (factor build); any output keeping the D axis -> an
    *expansion* stream (output assembly).  Taint does NOT flow through a
    consumer: its result is derived data, and a further pass over it is a
    new stream of that object, not of the argument being tracked.
    """
    for eqn in jaxpr.eqns:
        tin = any(_is_var(v) and v in tainted for v in eqn.invars)
        name = eqn.primitive.name
        if name in _STREAM_CONSUMERS:
            if tin:
                kind = ("expansion" if any(_axis_ge(v.aval, d)
                                           for v in eqn.outvars)
                        else "reduction")
                counts[kind] = counts.get(kind, 0) + 1
            continue  # opaque: no taint through, no recursion into bodies
        sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        inner = getattr(sub, "jaxpr", sub)
        if hasattr(inner, "eqns") and len(inner.invars) == len(eqn.invars):
            sub_taint = {iv for iv, ov in zip(inner.invars, eqn.invars)
                         if _is_var(ov) and ov in tainted}
            out_taint = _walk_streams(inner, sub_taint, d, counts)
            for outer_v, inner_v in zip(eqn.outvars, inner.outvars):
                if _is_var(inner_v) and inner_v in out_taint:
                    tainted.add(outer_v)
            continue
        if tin:
            for ov in eqn.outvars:
                if _axis_ge(ov.aval, d):
                    tainted.add(ov)
    return tainted


def count_data_streams(closed_jaxpr, argnum: int, d: int) -> dict:
    """{'reduction': r, 'expansion': e} streams of argument ``argnum``.

    The structural teeth behind the single-sweep claim (DESIGN.md sec. 12):
    tracing e.g. ``woodbury_solve`` as a function of X and counting the
    contractions that consume X (or anything elementwise-derived from it,
    pads and casts included) proves the lowered program reads the data
    stream exactly once to build factors (``reduction == 1``) plus the one
    unavoidable output-assembly stream (``expansion``) — a refactor that
    reintroduces a separate norms/S/RHS pass flips the count.  ``d`` is
    the data axis length; derived (N, N) objects must all be smaller, so
    pick shapes with max(N, Q)**2 < d when tracing.
    """
    jaxpr = closed_jaxpr.jaxpr
    counts: dict = {"reduction": 0, "expansion": 0}
    _walk_streams(jaxpr, {jaxpr.invars[argnum]}, d, counts)
    return counts


def count_primitive(jaxpr, name: str) -> int:
    """Recursively count occurrences of a jax primitive in a jaxpr.

    Walks into nested jaxprs (pjit/cond/scan/while bodies). Used to assert
    structural invariants — e.g. that the fused Gram MVM compiles to
    exactly ONE pallas_call (a Pallas kernel can only round-trip HBM
    through declared outputs, so the launch count pins the transfer model
    of DESIGN.md 4.3).
    """
    import jax

    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            count += 1
        for v in eqn.params.values():
            for leaf in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: hasattr(x, "jaxpr") or hasattr(x, "eqns")):
                inner = getattr(leaf, "jaxpr", leaf)
                if hasattr(inner, "eqns"):
                    count += count_primitive(inner, name)
    return count


def count_psums(closed_jaxpr) -> int:
    """Number of ``psum`` equations in a traced shard_map program.

    The one-psum-per-phase gate of the D-sharded state machine
    (``core/dist_state.py``, DESIGN.md sec. 14): a multi-operand
    ``jax.lax.psum(tuple, ...)`` is ONE fused psum equation, so this count
    is exactly the number of collective launches a phase issues — extend
    <= 1, evict == 0, lengthscale refactor == 0, resolve/query == 1.
    Counts trace-level structure; lax.cond/switch bodies are all counted,
    so gate the per-phase functions, not a branchy step that traces
    every alternative.
    """
    return count_primitive(closed_jaxpr.jaxpr, "psum")


# ---------------------------------------------------------------------------
# Jaxpr shape census: axis/size bounds for structural never-dense gates
# ---------------------------------------------------------------------------


def jaxpr_axis_sizes(jaxpr) -> list:
    """Every integer axis size appearing on any var of ``jaxpr`` (recursing
    into sub-jaxprs).  The census behind the structural never-dense gates:
    ``hyper.mll.assert_no_dense_gram`` (exact regime, N < D) and
    ``regime.krylov.assert_streaming_structure`` (iterative regime, N > D).
    """
    dims: list = []
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", ())
            dims.extend(int(s) for s in shape if isinstance(s, int))
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    dims.extend(jaxpr_axis_sizes(inner))
    return dims


def jaxpr_var_sizes(jaxpr) -> list:
    """Total element count of every var of ``jaxpr`` (recursing into
    sub-jaxprs).  Catches square dense objects whose individual axes are
    individually legal — an (ND, ND) matrix has axis ND (same as a mere
    vec flattening) but ND^2 elements."""
    import math as _math

    sizes: list = []
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", ())
            if all(isinstance(s, int) for s in shape):
                sizes.append(int(_math.prod(shape)) if shape else 1)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    sizes.extend(jaxpr_var_sizes(inner))
    return sizes
