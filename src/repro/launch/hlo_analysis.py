"""Loop-aware analysis of optimized (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` counts every computation ONCE — a
``lax.scan`` over 40 layers or 16 microbatches is a ``while`` loop whose
body contributes a single trip to the reported FLOPs/bytes.  For a
scan-over-layers transformer that underestimates compute by >100×, which
would make any roofline built on it meaningless.

This module re-derives the costs from the optimized HLO itself:

  1. split the module text into named computations;
  2. build the call graph (fusion ``calls=``, ``while`` body/condition with
     ``backend_config={"known_trip_count":{"n":N}}``, ``conditional``
     branches) and propagate a trip **multiplier** from ENTRY down;
  3. FLOPs: every ``dot`` contributes 2·|out|·K (K = contracted extent,
     read off the lhs operand's shape and ``lhs_contracting_dims``),
     weighted by its computation's multiplier;
  4. HBM traffic: every *materializing* top-level op (fusion, dot,
     collective, copy, slice/update, gather/scatter, reduce, …)
     contributes operand+output bytes — the between-fusions boundary is
     exactly what XLA spills to HBM;
  5. collective bytes: output sizes of communication ops, same weighting.

Conditionals count every branch at full weight (upper bound; the FedDec
server round is the only cond in these graphs and it is cheap).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Iterable

import numpy as np

__all__ = ["HloCosts", "analyze_hlo", "CollectiveAxes", "collective_axes",
           "axis_separation"]

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "u64": 8,
    "s64": 8, "f64": 8, "c64": 8, "c128": 16, "token": 0, "f8e4m3fn": 1,
    "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops whose operands/outputs cross an HBM boundary
_MATERIALIZING = (
    "fusion", "dot", "convolution", "copy", "dynamic-slice",
    "dynamic-update-slice", "gather", "scatter", "reduce", "sort",
    "transpose", "reshape", "broadcast", "iota", "pad", "concatenate",
    "slice", "select-and-scatter", "reduce-window", "rng-bit-generator",
    "cholesky", "triangular-solve",
) + _COLL_KINDS

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_START_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-_]+)\s*\(.*->.*\{\s*$")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-_]+)\s*=\s*(\([^)]*\)|\w+\[[\d,]*\]\S*)\s+"
    r"([\w\-]+)\((.*)$")
_PARAM_RE = re.compile(r"%?([\w.\-_]+):\s*(\([^)]*\)|\w+\[[\d,]*\]\S*)")
_TRIP_RE = re.compile(r'known_trip_count[\\"{:n\s]*?(\d+)')
_CALLS_RE = re.compile(r"calls=%?([\w.\-_]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-_]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-_]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRUE_RE = re.compile(r"true_computation=%?([\w.\-_]+)")
_FALSE_RE = re.compile(r"false_computation=%?([\w.\-_]+)")
_OPERANDS_RE = re.compile(r"%([\w.\-_]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _shape_elems_bytes(type_str: str) -> tuple[int, int]:
    """Total (elements, bytes) across a possibly-tuple type string."""
    total_e = total_b = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        e = int(np.prod([int(d) for d in dims.split(",") if d])) \
            if dims else 1
        total_e += e
        total_b += e * _DTYPE_BYTES[dt]
    return total_e, total_b


@dataclasses.dataclass
class _Op:
    name: str
    kind: str
    type_str: str
    rest: str          # text after the opening paren (operands + attrs)


@dataclasses.dataclass
class _Computation:
    name: str
    ops: list[_Op]
    symbols: dict[str, str]   # value name -> type string


def _parse_computations(text: str) -> dict[str, _Computation]:
    comps: dict[str, _Computation] = {}
    cur: _Computation | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_START_RE.match(line.strip())
            if m and "{" in line:
                cur = _Computation(m.group(1), [], {})
                # parameters declared in the signature
                for pname, ptype in _PARAM_RE.findall(line):
                    cur.symbols[pname] = ptype
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            name, type_str, kind, rest = m.groups()
            cur.symbols[name] = type_str
            cur.ops.append(_Op(name, kind, type_str, rest))
    return comps


def _dot_flops(op: _Op, comp: _Computation) -> float:
    out_elems, _ = _shape_elems_bytes(op.type_str)
    mc = _LHS_CONTRACT_RE.search(op.rest)
    operands = _OPERANDS_RE.findall(op.rest.split("),")[0] + ")")
    if not operands:
        return 0.0
    lhs_type = comp.symbols.get(operands[0], "")
    sm = _SHAPE_RE.search(lhs_type)
    if not sm:
        return 0.0
    dims = [int(d) for d in sm.group(2).split(",") if d]
    if mc:
        cdims = [int(d) for d in mc.group(1).split(",") if d]
        k = int(np.prod([dims[d] for d in cdims])) if cdims else 1
    else:
        k = dims[-1] if dims else 1
    return 2.0 * out_elems * k


def _operand_bytes(op: _Op, comp: _Computation) -> int:
    # operands are the leading %refs before attribute keywords
    head = op.rest
    for stop in ("calls=", "condition=", "to_apply=", "metadata=",
                 "backend_config=", "dimensions=", "lhs_contracting",
                 "sharding=", "channel_id="):
        idx = head.find(stop)
        if idx != -1:
            head = head[:idx]
    total = 0
    for ref in _OPERANDS_RE.findall(head):
        t = comp.symbols.get(ref)
        if t:
            total += _shape_elems_bytes(t)[1]
    return total


_META_RE = re.compile(r'op_name="([^"]*)"')

# replica_groups comes in two syntaxes post-SPMD: the literal nested-brace
# form ``replica_groups={{0,2},{1,3}}`` and the iota ("V2") form
# ``replica_groups=[G,S]<=[d0,d1]T(p0,p1)`` — arange over [d0,d1,...],
# transposed by the optional perm, reshaped to (G, S) rows-as-groups.
_RG_LITERAL_RE = re.compile(r"replica_groups=\{((?:\{[\d,\s]*\},?\s*)*)\}")
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_STP_RE = re.compile(r"source_target_pairs=\{((?:\{[\d,\s]*\},?\s*)*)\}")
_GROUP_RE = re.compile(r"\{([\d,\s]*)\}")


@dataclasses.dataclass
class HloCosts:
    flops: float = 0.0
    traffic_bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_counts: dict = dataclasses.field(
        default_factory=lambda: {k: 0 for k in _COLL_KINDS})
    collective_bytes_by_kind: dict = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in _COLL_KINDS})


def analyze_hlo(text: str, entry: str | None = None) -> HloCosts:
    """Trip-count-weighted FLOPs / HBM traffic / collective bytes."""
    comps = _parse_computations(text)
    if entry is None:
        m = re.search(r"^ENTRY\s+%?([\w.\-_]+)", text, re.M)
        entry = m.group(1) if m else next(iter(comps))
    costs = HloCosts()
    # iterative worklist: (computation, multiplier, fused?).  Computations
    # reachable from several sites accumulate each site's weight.  fused=True
    # marks bodies of fusion/custom-call/reduce etc. — their internals live
    # in registers, so they contribute FLOPs but NOT HBM traffic (counting
    # them as traffic double-books the enclosing fusion op's operands).
    work: list[tuple[str, float, bool]] = [(entry, 1.0, False)]
    guard = 0
    while work:
        guard += 1
        if guard > 200_000:
            raise RuntimeError("HLO call graph traversal did not terminate")
        cname, mult, fused = work.pop()
        comp = comps.get(cname)
        if comp is None:
            continue
        for op in comp.ops:
            if op.kind == "while":
                trips = 1
                tm = _TRIP_RE.search(op.rest)
                if tm:
                    trips = int(tm.group(1))
                bm = _BODY_RE.search(op.rest)
                cm = _COND_RE.search(op.rest)
                if bm:
                    work.append((bm.group(1), mult * trips, fused))
                if cm:
                    work.append((cm.group(1), mult * (trips + 1), fused))
                continue
            if op.kind == "conditional":
                brm = _BRANCHES_RE.search(op.rest)
                names: Iterable[str] = []
                if brm:
                    names = _OPERANDS_RE.findall(brm.group(1))
                else:
                    names = [g.group(1) for g in
                             (_TRUE_RE.search(op.rest),
                              _FALSE_RE.search(op.rest)) if g]
                for nm in names:
                    work.append((nm, mult, fused))
                continue
            if op.kind == "call":
                cm2 = _CALLS_RE.search(op.rest) or \
                    re.search(r"to_apply=%?([\w.\-_]+)", op.rest)
                if cm2:
                    work.append((cm2.group(1), mult, fused))
            elif op.kind in ("fusion", "custom-call", "reduce", "sort",
                             "scatter", "select-and-scatter",
                             "reduce-window", "map", "all-reduce",
                             "reduce-scatter"):
                cm2 = _CALLS_RE.search(op.rest) or \
                    re.search(r"to_apply=%?([\w.\-_]+)", op.rest)
                if cm2:
                    work.append((cm2.group(1), mult, True))
            if op.kind == "dot":
                costs.flops += mult * _dot_flops(op, comp)
            if fused:
                continue  # register-resident: no HBM traffic, no collectives
            if op.kind in _COLL_KINDS or any(
                    op.kind == k + "-start" for k in _COLL_KINDS):
                kind = op.kind.removesuffix("-start")
                _, out_b = _shape_elems_bytes(op.type_str)
                costs.collective_counts[kind] += 1
                costs.collective_bytes_by_kind[kind] += mult * out_b
                costs.collective_bytes += mult * out_b
            if op.kind in _MATERIALIZING or op.kind.endswith("-start"):
                _, out_b = _shape_elems_bytes(op.type_str)
                costs.traffic_bytes += mult * (out_b
                                               + _operand_bytes(op, comp))
    return costs


# ---------------------------------------------------------------------------
# 2-D mesh axis classification
#
# The 2-D ('agents', 'model') lowering promises a clean separation: gossip /
# server collectives communicate only along the agent axis while the
# tensor-parallel matmul (and loss) collectives communicate only along the
# model axis.  ``collective_axes`` proves it from the optimized HLO — it
# parses every collective's device groups and classifies them against the
# row-major (A, M) device layout ``id = a * M + m`` that
# ``launch.mesh.make_fed_mesh`` produces:
#
#   * a group is **model**-only iff every id in it shares ``id // M``
#     (same agent replica, varying model shard);
#   * a group is **agents**-only iff every id shares ``id % M``
#     (same model shard, varying agent);
#   * a collective-permute pair (src, tgt) is agents-only iff
#     ``src % M == tgt % M`` and model-only iff ``src // M == tgt // M``;
#   * anything else is **mixed** — the failure the tests guard against.
# ---------------------------------------------------------------------------


def _parse_replica_groups(rest: str, n_devices: int) -> list | None:
    """Device groups of a collective op line, or None if absent."""
    im = _RG_IOTA_RE.search(rest)
    if im:
        shape = [int(x) for x in im.group(1).split(",") if x]
        dims = [int(x) for x in im.group(2).split(",") if x]
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if im.group(3):
            arr = np.transpose(arr,
                               [int(x) for x in im.group(3).split(",") if x])
        return [[int(i) for i in row] for row in arr.reshape(shape)]
    lm = _RG_LITERAL_RE.search(rest)
    if lm:
        groups = [[int(x) for x in g.split(",") if x.strip()]
                  for g in _GROUP_RE.findall(lm.group(1))]
        # ``replica_groups={}`` means one group of every device
        return groups if groups else [list(range(n_devices))]
    return None


def _axis_of_groups(groups: list, m: int) -> str:
    axes = set()
    for g in groups:
        if len(g) <= 1:
            continue
        if all(i // m == g[0] // m for i in g):
            axes.add("model")
        elif all(i % m == g[0] % m for i in g):
            axes.add("agents")
        else:
            axes.add("mixed")
    if not axes:
        return "single"
    return axes.pop() if len(axes) == 1 else "mixed"


def _axis_of_pairs(pairs: list, m: int) -> str:
    axes = set()
    for s, t in pairs:
        if s == t:
            continue
        if s // m == t // m:
            axes.add("model")
        elif s % m == t % m:
            axes.add("agents")
        else:
            axes.add("mixed")
    if not axes:
        return "single"
    return axes.pop() if len(axes) == 1 else "mixed"


@dataclasses.dataclass
class CollectiveAxes:
    """One collective op with its parsed groups and mesh-axis verdict."""
    kind: str                 # all-reduce / reduce-scatter / ...
    axis: str                 # 'agents' | 'model' | 'mixed' | 'single' | 'unknown'
    groups: list | None       # replica groups (None for collective-permute)
    pairs: list | None        # (src, tgt) pairs (collective-permute only)
    op_name: str              # metadata origin, for debugging


def collective_axes(text: str, n_agent_shards: int,
                    n_model_shards: int) -> list[CollectiveAxes]:
    """Classify every collective in ``text`` against the (A, M) mesh.

    Scans all computations (while bodies included), so collectives inside
    the fused-round scan are covered.  ``-done`` halves of async pairs carry
    no groups and are skipped; ``-start`` halves classify normally.
    """
    a, m = int(n_agent_shards), int(n_model_shards)
    ndev = a * m
    out: list[CollectiveAxes] = []
    for comp in _parse_computations(text).values():
        for op in comp.ops:
            kind = op.kind.removesuffix("-start")
            if kind not in _COLL_KINDS:
                continue
            om = _META_RE.search(op.rest)
            origin = om.group(1) if om else ""
            if kind == "collective-permute":
                sm = _STP_RE.search(op.rest)
                if not sm:
                    out.append(CollectiveAxes(kind, "unknown", None, None,
                                              origin))
                    continue
                pairs = [tuple(int(x) for x in g.split(",") if x.strip())
                         for g in _GROUP_RE.findall(sm.group(1))]
                out.append(CollectiveAxes(kind, _axis_of_pairs(pairs, m),
                                          None, pairs, origin))
            else:
                groups = _parse_replica_groups(op.rest, ndev)
                axis = (_axis_of_groups(groups, m)
                        if groups is not None else "unknown")
                out.append(CollectiveAxes(kind, axis, groups, None, origin))
    return out


def axis_separation(text: str, n_agent_shards: int,
                    n_model_shards: int) -> dict[str, list[str]]:
    """Axis -> sorted collective kinds found on it.

    The tentpole assertion reads: ``'mixed' not in sep`` and the gossip
    kinds (reduce-scatter / collective-permute) appear only under
    ``sep['agents']`` while the matmul/loss all-reduce appears under
    ``sep['model']``.
    """
    rep: dict[str, set] = {}
    for c in collective_axes(text, n_agent_shards, n_model_shards):
        rep.setdefault(c.axis, set()).add(c.kind)
    return {k: sorted(v) for k, v in rep.items()}
