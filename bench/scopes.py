"""Which device ops of a compiled program ran under a ``jax.named_scope``.

A TPU trace names each device op by its HLO instruction (``while.27``,
``fusion.2``) and carries no ``op_name`` metadata, so a stage marked with
``jax.named_scope`` in the program cannot be read off the trace alone. The
compiled program's HLO text (``jitted.lower(...).compile().as_text()``)
holds both: every instruction with its name and its
``metadata={op_name="jit(f)/<scope>/..."}``. ``parse`` reads that text;
``outermost`` gives the instructions under a scope that no other
instruction under it contains (a ``while`` inside a ``while`` body, the
ops of a loop body), so that summing their device time counts each stretch
of device time once; ``device_seconds`` does that sum over a reduced
trace's per-op seconds (``bench/trace.py`` ``Reduced.op_seconds``, keyed
``"<program>/<instruction>"``).
"""
from __future__ import annotations

import re
from typing import NamedTuple

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation"
                    r"|false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:called_computations|branch_computations)"
                         r"=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class Program(NamedTuple):
    module: str           # the HLO module's name, as the trace names it
    op_names: dict        # instruction -> its op_name metadata ("" if none)
    parent: dict          # instruction -> the instruction whose called
    #                       computation holds it (absent at the entry)


def parse(hlo_text: str) -> Program:
    """Instructions, their ``op_name`` and their enclosing instruction, from
    a compiled program's HLO text."""
    lines = hlo_text.splitlines()
    module = lines[0].split()[1].rstrip(",") if lines else ""
    op_names, home, callers = {}, {}, {}
    computation = None
    for line in lines[1:]:
        head = _COMPUTATION.match(line)
        if head and not line.startswith(" "):
            computation = head.group(1)
            continue
        inst = _INSTRUCTION.match(line)
        if inst is None or computation is None:
            continue
        name = inst.group(1)
        meta = _OP_NAME.search(line)
        op_names[name] = meta.group(1) if meta else ""
        home[name] = computation
        called = _CALLS.findall(line)
        for group in _CALL_LISTS.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        for c in called:
            callers.setdefault(c, name)
    parent = {n: callers[c] for n, c in home.items() if c in callers}
    return Program(module=module, op_names=op_names, parent=parent)


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``op_name`` lies under the named scope ``scope``: one of its
    "/"-separated parts is the scope, bare or inside transform wrappers
    (``jvp(gcd)``)."""
    return any(re.fullmatch(r"(?:[\w.\-]+\()*" + re.escape(scope) + r"\)*",
                            part) for part in op_name.split("/"))


def outermost(program: Program, scope: str) -> set:
    """Instructions under ``scope`` that no instruction under it encloses."""
    under = {n for n, op in program.op_names.items() if in_scope(op, scope)}
    out = set()
    for name in under:
        up = program.parent.get(name)
        while up is not None and up not in under:
            up = program.parent.get(up)
        if up is None:
            out.add(name)
    return out


def ran_here(program: Program, op_seconds: dict) -> bool:
    """Whether every op a trace timed under the program's name is one of
    its instructions, and at least one was timed: the check that the
    compiled text is the program that ran."""
    prefix = program.module + "/"
    timed = {n[len(prefix):] for n in op_seconds if n.startswith(prefix)}
    return bool(timed) and timed <= program.op_names.keys()


def device_seconds(program: Program, names, op_seconds: dict):
    """Summed device seconds of ``names`` (instructions of ``program``) in a
    reduced trace's ``op_seconds``; None when none of them ran."""
    hit = [op_seconds[k] for k in (f"{program.module}/{n}" for n in names)
           if k in op_seconds]
    return sum(hit) if hit else None
