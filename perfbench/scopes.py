"""Device time by phase of Algorithm 1, from the program's named scopes.

The program runs each phase of a round under a ``jax.named_scope`` named
``feddec.<phase>`` (``core/engine.py:build_step_body``: ``sample_w``,
``update``, ``mix``, ``update_mix``, ``server``; ``core/flat.py:FlatSpec``:
``unflatten``, ``flatten``; ``models/model.py:Model.grad_fn``: ``grad``).
The scope reaches the ``op_name`` metadata of every instruction that the
phase compiles to, and a fusion carries the ``op_name`` of its root.  An
instruction's phase is the last ``feddec.*`` component of its ``op_name``,
so the grad, flatten and unflatten ops inside ``feddec.update_mix`` count
as their own phases.

The compiler also makes instructions of its own that carry no
``op_name``: copies into a loop's carry, a concatenate rewritten as a
chain of dynamic-update-slices, loops that move a large operand piece by
piece.  Such an instruction takes, in this order: the phase of the
instruction whose computation holds it (the loop, call or fusion); else
the one phase of the nearest instructions with an ``op_name`` that use
its result, since the compiler made it for them; else the one phase of
the nearest it reads.  Where those disagree or none has a phase, it has
none.  The compiled text's instruction names are the ``hlo_op`` names of
the device trace (``trace.py``), which joins the two.

``grad_flops_pct`` and ``flat_copy_pct`` read a context of the readers'
kind (``metrics/``) that also holds ``scopes``, the map ``hlo_scopes``
makes of the round's compiled text.
"""

from __future__ import annotations

import re

from perfbench import trace as trace_lib

__all__ = ["PREFIX", "PHASES", "hlo_scopes", "phase_ns", "phase_table",
           "grad_flops_pct", "flat_copy_pct"]

PREFIX = "feddec."
PHASES = ("sample_w", "update", "mix", "update_mix", "server", "unflatten",
          "flatten", "grad")
FLAT_COPIES = ("unflatten", "flatten")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_OPERANDS = re.compile(r"\b[a-z][\w\-]*\(([^()]*)\)(?:,|$)")
_NAME = re.compile(r"%([\w.\-]+)")
_PHASE = re.compile(re.escape(PREFIX) + r"(\w+)")


def hlo_scopes(compiled_text: str) -> dict:
    """{HLO instruction name: its phase, or None} of a compiled program."""
    own, home, caller, reads, users = {}, {}, {}, {}, {}
    computation = None
    for line in compiled_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            computation = c.group(1) if c else computation
            continue
        name, rhs = m.group(1), line[m.end():]
        home[name] = computation
        op_name = _OP_NAME.search(line)
        if op_name:
            phases = _PHASE.findall(op_name.group(1))
            own[name] = phases[-1] if phases else None
        called = _CALLS.findall(line) + [
            c.strip().lstrip("%") for group in _CALL_LISTS.findall(line)
            for c in group.split(",")]
        for c in called:
            caller.setdefault(c, name)
        operands = _OPERANDS.search(rhs)
        reads[name] = _NAME.findall(operands.group(1)) if operands else []
        for r in reads[name]:
            users.setdefault(r, []).append(name)

    def nearest(name, step):
        """Phases of the nearest instructions with an op_name along
        ``step`` (users or operands), through those without one."""
        found, seen, todo = set(), {name}, list(step.get(name, ()))
        while todo:
            n = todo.pop()
            if n in seen:
                continue
            seen.add(n)
            if n in own:
                found.add(own[n])
            else:
                todo += step.get(n, ())
        return found

    memo = {}

    def phase(name):
        if name in own:
            return own[name]
        if name not in memo:
            up = caller.get(home.get(name))
            p = phase(up) if up else None
            for step in (users, reads):
                if p is None:
                    found = nearest(name, step)
                    p = found.pop() if len(found) == 1 else None
            memo[name] = p
        return memo[name]

    return {name: phase(name) for name in home}


def phase_ns(ops, scopes: dict, phases, lo: int, hi: int) -> int:
    """Device time in [lo, hi) in which an op of one of ``phases`` runs:
    the union of their intervals."""
    phases = set(phases)
    return trace_lib.busy_ns([o for o in ops if scopes.get(o.hlo_op)
                              in phases], lo, hi)


def phase_table(ops, scopes: dict, lo: int, hi: int, top: int = 10) -> dict:
    """Seconds of each phase in [lo, hi), of the device's busy time, of the
    busy time in no phase, and the ``top`` ops of that remainder by HLO
    name with their seconds."""
    busy = trace_lib.busy_ns(ops, lo, hi)
    rest = [o for o in ops if scopes.get(o.hlo_op) not in PHASES]
    return {"phases_s": {p: phase_ns(ops, scopes, (p,), lo, hi) / 1e9
                         for p in PHASES},
            "busy_s": busy / 1e9,
            "no_phase_s": trace_lib.busy_ns(rest, lo, hi) / 1e9,
            "no_phase_ops": trace_lib.top_ops(rest, lo, hi, n=top)}


def grad_flops_pct(ctx):
    """Forward and backward's own share of the bf16 peak: the model FLOPs
    of the window's steps over the phase-``grad`` device seconds, summed
    over the chips, times the peak.  None where no ``grad`` op ran."""
    if "scopes" not in ctx or not ctx["device_ids"]:
        return None
    ns = sum(phase_ns(ctx["trace"].devices[i], ctx["scopes"], ("grad",),
                      ctx["lo"], ctx["hi"]) for i in ctx["device_ids"])
    if not ns:
        return None
    return 100.0 * ctx["steps"] * ctx["flops_per_step"] / (
        ns / 1e9 * ctx["peaks"]["bf16_flops"])


def flat_copy_pct(ctx):
    """Share of the traced window in which the chips run the flat buffer's
    ``unflatten`` or ``flatten`` ops, averaged over the chips; 0.0 where
    the program runs none."""
    if "scopes" not in ctx or not ctx["device_ids"]:
        return None
    ns = [phase_ns(ctx["trace"].devices[i], ctx["scopes"], FLAT_COPIES,
                   ctx["lo"], ctx["hi"]) for i in ctx["device_ids"]]
    return 100.0 * sum(ns) / len(ns) / (ctx["hi"] - ctx["lo"])
