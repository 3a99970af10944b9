"""Device time of the reservoir ``sample`` op's program (``jit_sample``)
in the traced window, per traced batch, in milliseconds."""

from bench.progtrace import program_ms_per_item


def read(run):
    return program_ms_per_item(run, "jit_sample")
