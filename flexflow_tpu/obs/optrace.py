"""Which FlexFlow operator, and which pass, a compiled instruction
belongs to.

``FFModel._apply`` runs every operator's forward under
``jax.named_scope(op.name)``, every planned regrid under
``ff_regrid.<op>.<input>`` and every step factory its optimizer update
under ``ff_update``.  The names reach the compiled HLO as each
instruction's ``metadata={op_name="jit(ff_train_step)/jvp(conv1)/..."}``,
with JAX's own ``jvp(..)`` (forward, and the residuals it keeps for the
backward) and ``transpose(jvp(..))`` (backward) around them; inside a
block that ``jax.checkpoint`` recomputes the transformations stand on an
empty part before ``checkpoint/`` and ``rematted_computation/``, and the
scope's name follows bare (both are charged to the backward pass).  This module
reads them back from ``compiled.as_text()``; a device trace's events
carry only the instruction (checked on the v5e, PR 26: an ``XLA Ops``
event has its HLO text as name and no ``op_name`` among its stats), so a
trace reader joins the two by instruction name.

Pure text, no JAX.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, Optional, Tuple

UPDATE_SCOPE = "ff_update"
REGRID_PREFIX = "ff_regrid."

FORWARD, BACKWARD, UPDATE, REGRID, OTHER = (
    "forward", "backward", "update", "regrid", "other")
PASSES = (FORWARD, BACKWARD, UPDATE, REGRID, OTHER)

_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# a custom call that one of XLA's own rewrites put in place of a scoped
# instruction keeps a bare name of the rewrite's and loses the scope
# (``metadata={op_name="ragged-dot-none"}`` where ``jax.lax.ragged_dot``
# stood, seen in the step compiled for a v5e, PR 28)
_CUSTOM_CALL = re.compile(r" custom-call\((.*?)\), custom_call_target=")
_OPERAND = re.compile(r"%([\w.\-]+)")
# what JAX's transformations put around a scope's name
_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
# path parts that ``jax.checkpoint`` puts before the scopes of a block it
# recomputes: in the backward pass the block's instructions read
# ``transpose(jvp(jvp()))/checkpoint/[rematted_computation/]<scope>/..``,
# the transformations on an empty part and the scope's name bare
_RECOMPUTE_PARTS = frozenset({"checkpoint", "rematted_computation"})


def module_name(hlo_text: str) -> str:
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else ""


def classify(op_name: str, operators: Optional[Iterable[str]] = None
             ) -> Tuple[str, str]:
    """(operator, pass) of one ``op_name`` path.  ``operators``, when
    given, is the set of names that count as operators; without it the
    outermost named scope does."""
    parts = op_name.split("/")[:-1]        # the last part is the primitive
    transposed = False          # by a part that wraps no name of its own
    for part in parts:
        wrappers = []
        inner = part
        while True:
            m = _WRAPPER.match(inner)
            if not m:
                break
            wrappers.append(m.group(1))
            inner = m.group(2)
        if "jit" in wrappers or "pjit" in wrappers:
            continue
        if not inner:
            transposed = transposed or "transpose" in wrappers
            continue
        if inner in _RECOMPUTE_PARTS:
            continue
        if inner == UPDATE_SCOPE:
            return UPDATE_SCOPE, UPDATE
        if inner.startswith(REGRID_PREFIX):
            return inner, REGRID
        if operators is not None and inner not in operators:
            continue
        # a recomputed block's second forward runs in the backward pass
        # and is charged there, where its time is spent
        return inner, (BACKWARD if transposed or "transpose" in wrappers
                       else FORWARD)
    return "", OTHER


def operator_table(hlo_text: str,
                   operators: Optional[Iterable[str]] = None
                   ) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (operator, pass)} for every instruction of an
    optimized HLO module.  A fusion takes its own metadata (its root's);
    one without any takes what most of the instructions it calls carry.
    A custom call under a bare name that a rewrite of XLA's gave it
    takes what most of its operands carry.  An instruction no scope names
    maps to ``("", "other")``."""
    if operators is not None:
        operators = frozenset(operators)
    table: Dict[str, Tuple[str, str]] = {}
    members: Dict[str, list] = collections.defaultdict(list)
    pending = []                 # (instruction, called computation)
    rewritten = []               # (custom call with a bare name, operands)
    computation = ""
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        if meta:
            table[name] = classify(meta.group(1), operators)
            members[computation].append(table[name])
            if "/" not in meta.group(1):
                call = _CUSTOM_CALL.search(line)
                if call:
                    rewritten.append((name, _OPERAND.findall(call.group(1))))
        else:
            table[name] = ("", OTHER)
            called = _CALLS.search(line)
            if called:
                pending.append((name, called.group(1)))
    for name, called in pending:
        votes = collections.Counter(v for v in members.get(called, ())
                                    if v[1] != OTHER)
        if votes:
            table[name] = votes.most_common(1)[0][0]
    # in text order, so that a call fed by another (the rewrite's own
    # set-up call) finds it named already
    for name, operands in rewritten:
        votes = collections.Counter(
            table[o] for o in operands
            if table.get(o, ("", OTHER))[1] != OTHER)
        if votes:
            table[name] = votes.most_common(1)[0][0]
    return table
