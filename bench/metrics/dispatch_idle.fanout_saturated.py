"""Share of the traced window in which the program is inside
``Orchestrator.execute_batch`` (its ``s2ce.execute_batch`` span) and no
operation runs on the device, in percent."""

from bench.progtrace import dispatch_idle_pct


def read(run):
    return dispatch_idle_pct(run)
