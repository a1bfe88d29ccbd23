"""Device seconds of a traced window by FlexFlow operator, for readers
that select operators by name (``benchmarks/program_trace.py`` gives them
by pass)."""

from benchmarks.program_trace import program_facts


def operator_seconds(facts, pattern):
    """Seconds of the traced window in the operators whose name matches
    the compiled ``pattern``, all passes (a recomputed block's second
    forward runs in its backward pass and is counted there): self time on
    the ``XLA Ops`` line, mean of the devices.  None off the chip, where
    the operator table was refused, on a program without operator names
    and where no operator matches."""
    prog = program_facts(facts)
    if not prog or not prog["on_chip"] or not prog["steps"]:
        return None
    by_op = (prog["trace"] or {}).get("operator_s")
    if not by_op:
        return None
    hits = [s for (operator, _), s in by_op.items()
            if pattern.match(operator)]
    return sum(hits) if hits else None
