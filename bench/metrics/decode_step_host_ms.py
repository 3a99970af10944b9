"""Median host wall time of one call of the decode program (the
program's ``s2ce.serve.decode_step`` span) in the traced window, in
milliseconds. The runtime lets only so many programs be in flight; once
the host has filled that queue, each call waits for a slot, and this
reads about the device's time per step. A host that falls behind the
device reads its own cost per call."""

from bench.progtrace import DECODE_SPAN, span_median_ms


def read(run):
    return span_median_ms(run, DECODE_SPAN)
