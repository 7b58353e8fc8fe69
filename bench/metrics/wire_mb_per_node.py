"""Bytes one node puts on the wire per step, in MB, as the program's
exchange tally (repro.dist.collectives.wire_report) counts them while
the step is traced."""


def read(ctx):
    wire = ctx["wire"]
    return sum(wire.values()) / 1e6 if wire else None
