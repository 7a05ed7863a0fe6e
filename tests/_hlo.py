"""Compiling a serving program for a described chip and reading its
optimised HLO text: which instructions run only inside a ``conditional``'s
branch (``tests/test_chip_compile.py``,
``tests/test_chip_compile_retention.py``)."""

import re

import jax

_HEADER = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
# a computation an instruction runs whenever it runs itself; a
# conditional's ``branch_computations`` (of which it runs ONE) are not
_CALLS = re.compile(r"\b(?:to_apply|calls|body|condition)=%?([\w.\-]+)")


def compile_def(pdef, chip):
    """``pdef`` (a ``ProgramDef``) lowered against its own argument
    templates placed on ``chip`` (a sharding), and compiled."""
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        pdef.args)
    return pdef.builder().lower(*args).compile()


def ops_by_gate(hlo: str, op: str):
    """``(gated, ungated)``: how many ``op`` instructions sit where only
    a branch of a ``conditional`` leads, and how many the program reaches
    without entering one (from the entry computation through loop bodies,
    fusions and calls)."""
    comps, entry, name = {}, None, None
    for line in hlo.splitlines():
        head = _HEADER.match(line)
        if head:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    reached, todo = {entry}, [entry]
    while todo:
        for line in comps[todo.pop()]:
            for callee in _CALLS.findall(line):
                if callee not in reached:
                    reached.add(callee)
                    todo.append(callee)
    mark = f" {op}("
    total = sum(mark in line for lines in comps.values() for line in lines)
    ungated = sum(mark in line for c in reached for line in comps[c])
    return total - ungated, ungated
