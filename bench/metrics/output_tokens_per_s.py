"""Requested output tokens of the requests completed in the window over
the window's seconds. Filler rows and tokens decoded past a request's
length do not count."""

from bench.readers import rate


def read(run):
    return rate(run, "output_len")
