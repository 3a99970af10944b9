"""Mean host span of the control pass after each batch (``bench.control``:
topology_step, controller.observe, apply_decision, elastic_step)."""

from bench.readers import span_ms


def read(run):
    return span_ms(run, "bench.control")
