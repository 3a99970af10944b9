"""Events of the batches completed in the window over the window's
seconds; the window closes when its last batch is done."""

from bench.readers import rate


def read(run):
    return rate(run, "events")
