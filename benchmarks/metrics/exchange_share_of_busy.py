"""Layer: exchange. Device seconds of the trace's collective ops — the
exchange's ``all-to-all`` and the ``all-reduce`` of its overflow
signals — as a share of the busy seconds of the devices used. A label
is the HLO instruction's own name, which JAX takes from the primitive
(``all_to_all.31``) and XLA from the opcode (``all-reduce.37``): both
spellings are read. A cell that lists this metric and traces no
collective is an error, never 0."""

COLLECTIVES = ("all-to-all", "all_to_all", "all-reduce", "all_reduce")


def read(r):
    if r.trace is None:
        return None
    found = [s for label, s in r.trace.ops.items()
             if label.startswith(COLLECTIVES)]
    if not found:
        raise LookupError(
            f"no device op named {' or '.join(COLLECTIVES)} in the trace")
    return 100.0 * sum(found) / (r.trace.busy_s * r.trace.devices)
