"""Names under which the serving loop shows up in a profiler trace.

Host spans: the pump's phases are ``jax.profiler.TraceAnnotation`` spans
with the ``serve/`` prefix. They are written into the profiler's own
trace, on the device trace's clock, while a trace is active, and cost
next to nothing otherwise.

Named scopes: the megastep's parts run under ``jax.named_scope``, which
the compiler keeps as each instruction's ``op_name`` metadata. A device
trace names operations only by instruction (``fusion.41``), so
``op_scopes`` joins the two through the compiled program's text.
"""

from __future__ import annotations

import re
from collections import defaultdict, deque

# every span the pump opens, in the order one dispatch-ahead iteration
# meets them (``serve/admit`` nests inside ``serve/schedule``)
SPANS = ("serve/schedule", "serve/admit", "serve/wait", "serve/readout",
         "serve/sync", "serve/evict", "serve/megastep", "serve/results")
SCHEDULE, ADMIT, WAIT, READOUT, SYNC, EVICT, MEGASTEP, RESULTS = SPANS

# the megastep's top-level scopes, which together cover the program, and
# the scopes nested inside ``decoder``
SCOPES = ("page_plan", "decoder", "select", "reorder", "bundle")
NESTED_SCOPES = ("self_attn", "page_view", "cross_attn", "ffn", "lm_head")

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPERANDS = re.compile(r"\b[a-z][a-z0-9\-]*\(([^()]*)\)")
_NAME = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_path(op_name: str) -> str:
    """``jit(f)/cond/branch_0_fun/decoder/while/body/self_attn/dot`` ->
    ``decoder/self_attn``: the declared scopes among the op's name
    segments (its last segment is the primitive), or ``""`` when they do
    not start at a top-level scope (a reducer's body names only the
    innermost scope). An instruction the compiler merged from several
    ops lists their names joined by ``;``: the first one counts."""
    declared = SCOPES + NESTED_SCOPES
    first = op_name.split(";", 1)[0]
    path = [s for s in first.split("/")[:-1] if s in declared]
    return "/".join(path) if path and path[0] in SCOPES else ""


def _nearest(start, direct: dict, step) -> str | None:
    """Scope of the nearest instruction with one, walking ``step``
    (instruction -> neighbours) breadth-first from ``start``."""
    seen, todo = {start}, deque([start])
    while todo:
        n = todo.popleft()
        if n in direct:
            return direct[n]
        for nxt in step(n):
            if nxt not in seen:
                seen.add(nxt)
                todo.append(nxt)
    return None


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> scope path for one compiled HLO module's text
    (``compiled.as_text()``). A fusion carries the metadata of its root,
    so it takes the root's scope. An instruction the compiler added
    without metadata (a copy, a scatter emitted as a custom fusion) takes
    the scope of the nearest instruction that produces its input (a copy
    is charged to the part whose output it moves); failing that, of the
    nearest that consumes its result, or, for an instruction nobody
    consumes, of the instruction calling its computation (the loop or
    branch it belongs to). Instructions that reach no scope are left
    out."""
    comp_of, direct = {}, {}
    operands = {}
    users, callers = defaultdict(list), defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            m = _HEADER.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        comp_of[name] = comp
        ops = _OPERANDS.search(rest)
        operands[name] = _NAME.findall(ops.group(1) if ops else "")
        for operand in operands[name]:
            users[operand].append(name)
        for single, branches in _CALLED.findall(rest):
            for called in [single] if single else _NAME.findall(branches):
                callers[called].append(name)
        op_name = _OP_NAME.search(rest)
        path = scope_path(op_name.group(1)) if op_name else ""
        if path:
            direct[name] = path
    out = dict(direct)
    for name in comp_of:
        if name not in out:
            path = (_nearest(name, direct, lambda n: operands.get(n, ()))
                    or _nearest(name, direct, lambda n: users.get(n)
                                or callers.get(comp_of[n], ())))
            if path:
                out[name] = path
    return out
