"""What each operation of a compiled training program belongs to.

The device trace names an operation and nothing else (``fusion.6760``,
``attn.527``: the instruction names of the compiled program), but the
compiled program's text says where each instruction came from:
``metadata={op_name="..."}`` holds JAX's name stack, and JAX writes the
differentiation and remat structure into it unasked: ``jvp(M)/...`` the
first forward pass, ``transpose(jvp(M))/...`` the backward pass,
``.../checkpoint/rematted_computation/...`` a forward pass run again
under ``nn.remat``, with Flax's module path behind them. The loss and
the optimizer run under a named scope each (``core/step.py``;
``tracing.LOSS_SCOPE``, ``tracing.OPTIMIZER_SCOPE``), which is all that
tells them from the task scan's own slices.

``operation_table(text)`` turns ``compiled.as_text()`` into one row per
instruction that can appear on the trace's ``XLA Ops`` lane: those of
the entry computation and of every ``while`` / ``conditional`` / ``call``
body reachable from it, not those inside fused computations, which a
fusion's row speaks for. ``utils/profiler.py`` writes the table beside
the trace it belongs to; ``tools/step_breakdown.py`` and the
benchmark's ``step_*_ms`` readers join it to the trace by ``name``.
Text in, rows out: nothing here imports jax.
"""

import re

from elasticdl_tpu.observability.tracing import LOSS_SCOPE, OPTIMIZER_SCOPE

FORWARD, RECOMPUTE, BACKWARD, OPTIMIZER, OTHER, MIXED = (
    "forward", "recompute", "backward", "optimizer", "other", "mixed")
# The phases a step's device time is split into; ``mixed`` (a fusion
# over several of them) and ``other`` are what the split leaves.
SCOPED = (FORWARD, RECOMPUTE, BACKWARD, OPTIMIZER)

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
# Name-stack entries that are no Flax module: what control flow and
# ``nn.remat`` leave there (transformations are told by their
# parentheses: ``jvp(...)``, ``jit(...)``).
_WRAPPERS = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation"))
_LAYER_INDEX = re.compile(r"_\d+$")
_BRANCH = re.compile(r"branch_\d+_fun$")


def module_name(text: str) -> str:
    """The compiled module's name (``jit_multi_step``): the name on the
    trace's ``XLA Modules`` lane without its id."""
    found = _MODULE.match(text)
    return found.group(1) if found else ""


def _opcode(rest: str) -> str:
    """The opcode of ``<type> <opcode>(<operands>), ...``: the type is
    one token, or a tuple in (nested) parentheses."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    found = _OPCODE.match(rest)
    return found.group(1) if found else ""


def _computations(text: str):
    """({computation: [(name, opcode, op_name, called computations)]},
    the entry computation's name)."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        if current is None:
            header = _HEADER.match(line)
            if header:
                current = computations.setdefault(header.group(2), [])
                if header.group(1):
                    entry = header.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        rest = found.group(2)
        called = {kind: name for kind, name in _CALLED.findall(rest)}
        branches = _BRANCHES.search(rest)
        if branches:
            for i, name in enumerate(branches.group(1).split(",")):
                called[f"branch_{i}"] = name.strip().lstrip("%")
        op_name = _OP_NAME.search(rest)
        current.append((found.group(1), _opcode(rest),
                        op_name.group(1) if op_name else "", called))
    return computations, entry


def phase_of(origin: str) -> str:
    """The phase one origin of an ``op_name`` says."""
    if "rematted_computation" in origin:
        return RECOMPUTE
    if "transpose(" in origin:
        return BACKWARD
    if "jvp(" in origin:
        return FORWARD
    if OPTIMIZER_SCOPE in origin.split("/"):
        return OPTIMIZER
    return OTHER


def _names_an_operation(origin: str) -> bool:
    """Whether an origin names an operation of the step. Two kinds do
    not, and say nothing about a fusion they were put into: a name stack
    that ends in the enclosing call itself
    (``jit(multi_step)/while/body/closed_call``: the constants the scan's
    body closes over and their broadcasts), and a bare primitive with no
    stack at all (``gather``, ``sort``, ``reduce_sum``,
    ``ragged-dot-none``: what XLA's own expansions leave where JAX's
    name was; an entry parameter's ``state.params[...]`` likewise)."""
    head, _, last = origin.rpartition("/")
    return bool(head) and "(" not in last and last not in _WRAPPERS


def phases_of(op_name: str):
    """The phases of an ``op_name``'s origins (``a;b`` holds several)."""
    return {phase_of(origin) for origin in op_name.split(";")
            if _names_an_operation(origin)}


def module_of(op_name: str) -> str:
    """The Flax path of an ``op_name``'s first origin: what follows the
    last differentiation wrapper (each ``jvp(...)`` / ``transpose(...)``
    restates the stack it wrapped, so a module's path would otherwise
    differ between the passes; one that wraps a function inside a
    module, as a custom rule's own ``jax.vjp`` of a conditional's branch
    does, restates nothing, and the path before it speaks), with the
    other transformations, control flow (a conditional's
    ``branch_<i>_fun`` too) and the trailing primitive dropped and layer
    indices folded
    (``blocks_3/attn/q_proj`` -> ``blocks_*/attn/q_proj``); ``loss`` and
    ``optimizer`` under their scopes; '' where nothing is left (the
    scan's own slices)."""
    origin = op_name.split(";")[0]
    if f"({LOSS_SCOPE})" in origin:
        return "loss"
    parts = origin.split("/")
    if OPTIMIZER_SCOPE in parts:
        return "optimizer"
    starts = [0] + [i + 1 for i, p in enumerate(parts)
                    if p.startswith(("jvp(", "transpose("))]
    for start in reversed(starts):
        named = [p for p in parts[start:-1]
                 if "(" not in p and p not in _WRAPPERS
                 and not _BRANCH.match(p)]
        if named:
            break
    return "/".join(_LAYER_INDEX.sub("_*", p) for p in named)


def _fused_phases(name, computations):
    """The phases of the instructions inside a fused computation (and
    of fusions nested in it); instructions XLA made with no metadata say
    nothing."""
    phases = set()
    for _, opcode, op_name, called in computations.get(name, ()):
        phases |= phases_of(op_name)
        if opcode == "fusion" and "calls" in called:
            phases |= _fused_phases(called["calls"], computations)
    return phases


def _reachable(computations, entry):
    """The computations whose instructions run as operations of their
    own: the entry and, from there, every ``while`` body and condition,
    ``conditional`` branch and ``call`` target."""
    order, todo = [], [entry]
    while todo:
        name = todo.pop()
        if name in order or name not in computations:
            continue
        order.append(name)
        for _, opcode, _, called in computations[name]:
            if opcode in ("while", "conditional", "call"):
                todo.extend(called.values())
    return order


def operation_table(text: str):
    """[{name, opcode, op_name, phase, module[, mixed]}] for the compiled
    program ``text`` (``compiled.as_text()``)."""
    computations, entry = _computations(text)
    rows = []
    for computation in _reachable(computations, entry):
        for name, opcode, op_name, called in computations[computation]:
            phases = phases_of(op_name)
            if opcode == "fusion" and "calls" in called:
                phases = _fused_phases(
                    called["calls"], computations) or phases
            row = {"name": name, "opcode": opcode, "op_name": op_name,
                   "phase": OTHER, "module": module_of(op_name)}
            if len(phases) == 1:
                row["phase"] = next(iter(phases))
            elif phases:
                row["phase"] = MIXED
                row["mixed"] = sorted(phases)
            rows.append(row)
    return rows


def table_of(text: str) -> dict:
    """What ``<profile_dir>/programs/<module>.ops.json`` holds."""
    return {"module": module_name(text), "ops": operation_table(text)}
