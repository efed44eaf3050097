"""Whose a nameless instruction is, from the compiled program's data flow.

The compiler adds instructions of its own to a step (copies, prefetches,
a loop's operands, broadcasts) and drops the metadata from some fusion
lines.  Those carry no ``op_name``, so ``scopes.phase`` puts their time
in ``unscoped``.  :func:`owners` gives each of them the ``op_name`` of
the named work its data flow reaches in ``compiled.as_text()``, by these
rules in order:

(a) a nameless fusion takes the ``op_name`` of its fused computation's
    root, failing that of the first instruction inside that has one (the
    compiler drops metadata from some fusion lines and keeps it inside);
(b) consumers first: the value's users, followed forward through
    :data:`PASS_THROUGH`; the nearest named user owns it, the first in
    text order among equally near ones.  A prefetch belongs to whoever
    waits for it, a loop's operand copy (``copy`` -> ``tuple`` ->
    ``while``) to the loop;
(c) then producers: the operands, followed back through the same
    pass-throughs; a called computation's parameter stands for the
    caller's operand, and an entry parameter ends the walk;
(d) then the caller: an instruction of a called computation (a loop's
    body or condition, a branch, a ``call``, a fused computation) takes
    the caller's ``op_name``, or what the caller inherits;
(e) otherwise ``None``.

"Named" in (a), (b) and (c) is an ``op_name`` that is a path under
``jit(...)`` (one that is none names an argument: a parameter's, or a
copy's the compiler named after one), the one (a) gives a fusion, and
then the owner (b) or (c) found for work that is no pass-through: the
walks run again for what is left until nothing changes, so a copy
between two nameless fusions takes the owner the fusions inherit.  The
attribution stays the program's: an inherited
``op_name`` goes through ``scopes.phase`` and ``scopes.block`` as it is,
so a copy that feeds a backward fusion counts as backward.  Pure
functions on strings; ``readers/train_step_unowned_share.py`` reads them.
"""

from __future__ import annotations

import collections
import re
from typing import NamedTuple

from benchmark import kda_scopes, moe_scopes, scopes, shortconv_scopes, trace

#: what only moves, casts or spreads a value: the walks of (b) and (c)
#: go through these, through every ``*-start`` / ``*-done`` pair, and
#: through a ``custom-call`` with no ``op_name`` (the compiler's own:
#: ``ConcatBitcast`` joins a parameter's slices, ``AllocateBuffer``);
#: a named one, a Mosaic kernel, is an owner
PASS_THROUGH = frozenset((
    "bitcast", "reshape", "transpose", "copy", "tuple", "get-tuple-element",
    "broadcast", "convert", "slice", "dynamic-slice", "dynamic-update-slice",
    "custom-call"))

#: the program's scopes (``apex_tpu/utils/profiling.py``) that label an
#: owner, beside the family's block segments
LABEL_SCOPES = frozenset(
    scopes.TRAIN_STEP_SCOPES + scopes.MODEL_SCOPES + moe_scopes.MOE_SCOPES
    + kda_scopes.KDA_SCOPES + shortconv_scopes.SHORTCONV_SCOPES)

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(")
_DEF = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"\b(?:calls|condition|body|to_apply|true_computation"
                    r"|false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)"
                         r"=\{([^}]*)\}")
_LAST_WORD = re.compile(r"([\w.\-]+)\s*$")
#: callers whose parameter ``i`` is their operand ``i``; a conditional's
#: branch ``k`` takes operand ``k + 1``
_PARAMETER_IS_OPERAND = ("fusion", "call", "while", "async-start")


class Instruction(NamedTuple):
    opcode: str
    operands: tuple      # names, in operand order
    called: tuple        # names of the computations it calls
    computation: str
    parameter: "int | None"


class Program(NamedTuple):
    instructions: dict   # name -> Instruction, in text order
    computations: dict   # name -> [instruction names], in text order
    roots: dict          # computation -> its root's name
    callers: dict        # computation -> (the first caller's name, index)
    op_names: dict       # what ``trace.op_names`` reads from the text


def _operands(line: str, start: int) -> "tuple[list, int]":
    """The operand list that opens at ``line[start] == '('``: its top-level
    pieces and the index of its closing parenthesis."""
    depth, pieces, begin = 0, [], start + 1
    for i in range(start, len(line)):
        c = line[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                pieces.append(line[begin:i])
                return pieces, i
        elif c == "," and depth == 1:
            pieces.append(line[begin:i])
            begin = i + 1
    return pieces, len(line)


def parse(hlo_text: str) -> Program:
    """The instructions of every computation in ``hlo_text``, with their
    operands and the computations they call."""
    raw, computations, roots = [], {}, {}
    computation = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            header = _HEADER.match(line)
            computation = header.group(1) if header else None
            if computation:
                computations[computation] = []
            continue
        d = _DEF.match(line)
        if not d or computation is None:
            continue
        name = d.group(2)
        m = _OPCODE.search(line, d.end() - 1)
        if not m:
            continue
        pieces, close = _operands(line, m.end() - 1)
        attributes = line[close + 1:].split("metadata={", 1)[0]
        called = _CALLS.findall(attributes)
        for group in _CALL_LISTS.findall(attributes):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        words = [w.group(1) for w in map(_LAST_WORD.search, pieces) if w]
        raw.append((name, m.group(1), words, tuple(called), computation))
        computations[computation].append(name)
        if d.group(1):
            roots[computation] = name
    defined = {c: set(names) for c, names in computations.items()}
    instructions, callers = {}, {}
    for name, opcode, words, called, computation in raw:
        parameter = int(words[0]) if opcode == "parameter" and words \
            and words[0].isdigit() else None
        operands = tuple(w for w in words if w in defined[computation])
        instructions[name] = Instruction(opcode, operands, called,
                                         computation, parameter)
        for k, callee in enumerate(called):
            callers.setdefault(callee, (name, k))
    return Program(instructions, computations, roots, callers,
                   trace.op_names(hlo_text))


def _passes(opcode: str) -> bool:
    """Whether a walk goes on through this instruction: a pass-through,
    or a parameter (which stands for its caller's operand)."""
    return opcode in PASS_THROUGH or opcode == "parameter" \
        or opcode.endswith(("-start", "-done"))


def _is_work(op_name: str) -> bool:
    """A path under ``jit(...)``; an ``op_name`` that is none (a
    parameter's, or that of a copy the compiler named after an argument,
    ``state.master_params['block_1']...``) names an argument, not work,
    and owns nothing."""
    return "/" in op_name


def _fusion_names(program: Program) -> dict:
    """Rule (a): the ``op_name`` a nameless fusion takes from inside."""
    out = {}
    for name, instr in program.instructions.items():
        if instr.opcode != "fusion" or name in program.op_names \
                or not instr.called:
            continue
        inside = program.computations.get(instr.called[0], ())
        root = program.roots.get(instr.called[0])
        found = [program.op_names.get(i, "") for i in (root, *inside)]
        found = next((op_name for op_name in found if _is_work(op_name)),
                     None)
        if found:
            out[name] = found
    return out


def _caller_operands(program: Program, instr: Instruction) -> tuple:
    """What a called computation's parameter stands for: the caller's
    operand, where the caller hands its operands on in order."""
    caller = program.callers.get(instr.computation)
    if caller is None or instr.parameter is None:
        return ()
    name, k = caller
    host = program.instructions[name]
    if host.opcode in _PARAMETER_IS_OPERAND:
        index = instr.parameter
    elif host.opcode == "conditional" and instr.parameter == 0:
        index = k + 1
    else:
        return ()
    return host.operands[index:index + 1]


def _nearest(start: str, neighbours, named: dict, instructions: dict,
             position: dict):
    """The nearest named instruction among ``start``'s neighbours,
    followed through pass-throughs; among equally near ones the first
    in text order."""
    seen, level = {start}, [start]
    while level:
        found = [n for x in level for n in neighbours(x) if n in named]
        if found:
            return named[min(found, key=position.__getitem__)]
        nxt = []
        for x in level:
            for n in neighbours(x):
                if n not in seen and _passes(instructions[n].opcode):
                    seen.add(n)
                    nxt.append(n)
        level = nxt
    return None


def inherit(program: Program) -> dict:
    """``{instruction name: inherited op_name or None}`` for every
    instruction of ``program`` whose line has no ``op_name``."""
    instructions = program.instructions
    named = {name: op_name for name, op_name in program.op_names.items()
             if name in instructions and _is_work(op_name)}
    fusions = _fusion_names(program)
    named.update(fusions)
    position = {name: i for i, name in enumerate(instructions)}
    users = collections.defaultdict(list)
    for name, instr in instructions.items():
        for operand in instr.operands:
            if users[operand][-1:] != [name]:
                users[operand].append(name)

    def producers(name):
        instr = instructions[name]
        if instr.opcode == "parameter":
            return _caller_operands(program, instr)
        return instr.operands

    out = dict(fusions)
    left = [name for name in instructions
            if name not in program.op_names and name not in fusions]
    while left:
        # the owner a nameless fusion or other work inherits counts as
        # its name for the walks of those still without one (a copy
        # between two nameless fusions); a pass-through's does not
        found = {name: _nearest(name, users.__getitem__, named,
                                instructions, position)
                 or _nearest(name, producers, named, instructions, position)
                 for name in left}
        out.update(found)
        named.update((k, v) for k, v in found.items()
                     if v and not _passes(instructions[k].opcode))
        still = [name for name in left if not found[name]]
        left = still if len(still) < len(left) else []
    for name, found in list(out.items()):
        if found is None:
            out[name] = _from_caller(program, name, named, out)
    return out


def _from_caller(program: Program, name: str, named: dict, out: dict):
    """Rule (d): the caller's ``op_name``, or what (b) and (c) gave the
    caller, climbing from caller to caller."""
    seen = set()
    computation = program.instructions[name].computation
    while computation in program.callers and computation not in seen:
        seen.add(computation)
        caller = program.callers[computation][0]
        found = named.get(caller) or out.get(caller)
        if found:
            return found
        computation = program.instructions[caller].computation
    return None


def owners(hlo_text: str) -> dict:
    """``{instruction name: inherited op_name}`` for every instruction of
    every computation of a compiled program whose line has no
    ``op_name``; ``None`` where nothing reaches a named instruction."""
    return inherit(parse(hlo_text))


def label(op_name: str, phase: str, family: str) -> str:
    """An owner as the notes name it: its phase and the deepest segment
    of its ``op_name`` that is a scope of the program's or a block of
    the family's (``backward/kda_recurrence``, ``forward/moe_dispatch``),
    or the phase alone where there is none."""
    marks = LABEL_SCOPES.union(*scopes.BLOCK_SEGMENTS.get(family, {}).values())
    for segment in reversed(scopes.segments(op_name)):
        if segment in marks:
            return f"{phase}/{segment}"
    return phase


class Split(NamedTuple):
    unscoped: float      # seconds in phase ``unscoped`` by the names alone
    unowned: float       # seconds that stay there with the owners
    by_owner: dict       # label -> seconds, of the unscoped ones owned
    unowned_by_instruction: dict


def split(run, owned: dict) -> Split:
    """Chip 0's ``unscoped`` seconds (``scopes.phase`` on the program's
    names) split by the owner each instruction inherits; what stays in
    ``unscoped`` with its inherited name is unowned."""
    unscoped = unowned = 0.0
    by_owner: dict = collections.defaultdict(float)
    left: dict = {}
    for instr, seconds in run.instruction_seconds.items():
        own = run.op_names.get(instr)
        if scopes.phase(instr, own) != "unscoped":
            continue
        unscoped += seconds
        name = own or owned.get(instr)
        phase = scopes.phase(instr, name)
        if phase == "unscoped":
            unowned += seconds
            left[instr] = seconds
        else:
            by_owner[label(name, phase, run.cfg["family"])] += seconds
    return Split(unscoped, unowned, dict(by_owner), left)
