"""Seconds from process start to the window's opening: imports, weights
or data made from the seed, compiles or compile-cache loads, warm-up."""


def read(run):
    return run.setup_s
