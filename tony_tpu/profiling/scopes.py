"""Which of the program's scopes an instruction of the compiled step belongs
to: the join between a device trace and the model's layers.

The program names the parts of a step with ``jax.named_scope``
(``tony.loss_and_grad`` and ``tony.optimizer`` in ``parallel/train.py``, a
``tony.<layer>[.<part>]`` where each layer's work happens in ``models/``),
and jax stamps ``jvp``, ``transpose(jvp(...))`` and ``checkpoint /
rematted_computation`` around them, so the ``op_name`` of every operation
XLA compiles says its pass and its layer. A device trace names an
operation by its instruction (``fusion.123``) and not by its ``op_name``;
``step_scopes`` reads the compiled module's text into the map from the one
to the other. Plain text in, plain data out: nothing here imports jax.

- ``scope_of(op_name)`` → ``(pass, scope)``: the one place that reads an
  ``op_name``.
- ``step_scopes(compiled_text)`` → the record ``jit_train_step`` leaves as
  the span ``user.step_scopes`` when a step is compiled ahead of time.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

#: the two scopes ``jit_train_step`` opens; every other ``tony.*`` segment
#: of an ``op_name`` is a layer's.
LOSS_AND_GRAD, OPTIMIZER = "tony.loss_and_grad", "tony.optimizer"
#: the passes under ``tony.loss_and_grad``; ``optimizer`` and ``other``
#: (outside both of the step's scopes) are the other two.
IN_THE_GRADIENT = ("forward", "backward", "recompute")
#: the scope of an operation that lies under no layer's.
NO_SCOPE = "-"

# A scope entered right under a transform reads ``jvp(tony.loss_head)``,
# ``transpose(jvp(tony.loss_head))``: jax wraps the first name inside it.
_LAYER_SCOPE = re.compile(
    r"^(?:[a-z_]+\()*(tony\.[a-z0-9_]+(?:\.[a-z0-9_]+)?)\)*$")


def scope_of(op_name: str) -> Tuple[str, str]:
    """``(pass, scope)`` of an operation from its ``op_name`` metadata.

    ``pass``: ``optimizer`` under ``tony.optimizer``; under
    ``tony.loss_and_grad``, ``recompute`` where the path holds
    ``rematted_computation`` (what ``jax.checkpoint`` runs again in the
    backward pass), else ``backward`` where a segment holds ``transpose(``,
    else ``forward``; ``other`` outside both. What a hand-written backward
    computes again (a ``custom_vjp``'s own recomputation) reads as
    ``backward``: jax marks only its own remat.

    ``scope``: the innermost ``tony.<layer>[.<part>]`` segment that is not
    one of the step's two, ``-`` where there is none.
    """
    parts = op_name.split("/")
    if OPTIMIZER in parts:
        which = "optimizer"
    elif LOSS_AND_GRAD not in parts:
        which = "other"
    elif "rematted_computation" in parts:
        which = "recompute"
    elif any("transpose(" in p for p in parts):
        which = "backward"
    else:
        which = "forward"
    layers = (m.group(1) for m in map(_LAYER_SCOPE.match, reversed(parts))
              if m and m.group(1) not in (LOSS_AND_GRAD, OPTIMIZER))
    return which, next(layers, NO_SCOPE)


#: opcodes that are no operation of the device's: they have a key for the
#: instructions that read them to inherit and no place in the record.
_NOT_RUN = frozenset(("parameter", "get-tuple-element", "tuple", "constant",
                      "bitcast"))

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_DEFINITION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_APPLIED = re.compile(r"\b(?:to_apply|select|scatter)=%?([\w.\-]+)")


def _closing(text: str, start: int) -> int:
    """Index of the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if not depth:
                return i
    return len(text)


def _instruction(line: str) -> Optional[tuple]:
    """``(name, opcode, operand names, rest of the line)`` of one
    instruction's line, None for any other line."""
    m = _DEFINITION.match(line)
    if not m:
        return None
    at = m.end()
    if line[at] == "(":                   # a tuple's type: skip it whole
        at = _closing(line, at) + 1
    else:
        at = line.find(" ", at)
    op = _OPCODE.match(line, at)
    if not op:
        return None
    end = _closing(line, op.end() - 1)
    return (m.group(1), op.group(1),
            _OPERAND.findall(line, op.end(), end), line[end:])


def step_scopes(compiled_text: str) -> dict:
    """The map of a compiled module (``compiled.as_text()``): every
    instruction the device may show on its ``XLA Ops`` line (parameters,
    constants, tuples, their elements and bitcasts are no operations), by
    the pass and scope of its ``op_name``.

    Returns ``{"module", "instructions", "inherited", "unscoped",
    "scopes": {"<pass>/<scope>": [instruction names]}, "with_update":
    [instruction names]}``. The instructions are those of every computation
    that is not a fusion's body or a reducer (a ``to_apply`` that is not a
    ``call``'s): the entry, loop bodies and conditions, branches, called
    computations. One whose ``op_name`` places it nowhere in the step
    (``other/-``: it carries none, as a copy or bitcast the compiler put
    in, or one without the step's path, as the ``reduce_window_sum`` a
    cumulative sum lowers to) takes the key of its one operand that has a
    place, where exactly one has (``inherited`` counts them). ``unscoped``
    counts the instructions under ``tony.loss_and_grad`` that no layer's
    scope covers.

    A fusion is one instruction with one ``op_name``, its root's, whatever
    else XLA fused into it: ``with_update`` lists the fusions of another
    pass whose body holds operations of ``tony.optimizer`` (a weight
    gradient's product with the leaf's update as its epilogue), so that a
    reader can say how much of the optimizer's work the ``optimizer`` pass
    does not show.
    """
    module = ""
    computations: List[Tuple[str, list]] = []
    inside: set = set()         # fusions' bodies and reducers
    updating: set = set()       # computations that hold an optimizer op
    for line in compiled_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _COMPUTATION.match(line)
        if m:
            computations.append((m.group(1), []))
            continue
        found = _instruction(line) if computations else None
        if found is None:
            continue
        name, opcode, operands, rest = found
        fused = None
        if opcode == "fusion":
            fused = _FUSED.search(rest)
            fused = fused and fused.group(1)
            inside.add(fused)
        elif opcode != "call":
            inside.update(_APPLIED.findall(rest))
        named = _OP_NAME.search(rest)
        op_name = named.group(1) if named else None
        if op_name and f"/{OPTIMIZER}/" in op_name:
            updating.add(computations[-1][0])
        computations[-1][1].append((name, opcode, operands, op_name, fused))

    nowhere = f"other/{NO_SCOPE}"
    keys: Dict[str, str] = {}
    scopes: Dict[str, List[str]] = {}
    with_update: List[str] = []
    total = inherited = 0
    for computation, instructions in computations:
        if computation in inside:
            continue
        for name, opcode, operands, op_name, fused in instructions:
            key = "/".join(scope_of(op_name)) if op_name else nowhere
            if key == nowhere:
                scoped = [keys[o] for o in operands
                          if keys.get(o, nowhere) != nowhere]
                if len(scoped) == 1:
                    key = scoped[0]
                    inherited += opcode not in _NOT_RUN
            keys[name] = key
            if opcode in _NOT_RUN:
                continue
            scopes.setdefault(key, []).append(name)
            total += 1
            if fused in updating and not key.startswith("optimizer/"):
                with_update.append(name)
    unscoped = sum(len(scopes.get(f"{which}/{NO_SCOPE}", ()))
                   for which in IN_THE_GRADIENT)
    return {"module": module, "instructions": total, "inherited": inherited,
            "unscoped": unscoped, "scopes": scopes,
            "with_update": with_update}
