"""The share of a jitted program's device time that some of its operations
take, in %: the summed device time of the ``XLA Ops`` events matching
``pattern`` over the summed device time of the ``XLA Modules`` events
matching ``module``, both over the traced stretch. Either missing =
nothing to read."""

from benchmark import trace


def read(ctx, *, pattern: str, module: str):
    ops = trace.op_durations_s(ctx["trace"], pattern)
    programs = trace.module_durations_s(ctx["trace"], module)
    if not ops or not programs or sum(programs) <= 0:
        return None
    return 100.0 * sum(ops) / sum(programs)
