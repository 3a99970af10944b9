"""Mean host span of ``execute_batch`` up to its outputs being ready
(``bench.execute``), per batch in the window: the op graph's time."""

from bench.readers import span_ms


def read(run):
    return span_ms(run, "bench.execute")
