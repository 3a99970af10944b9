"""Share of the traced window in which no operation ran on the device,
in percent: 100 * (1 - busy_s / window_s)."""

from bench.readers import idle_pct


def read(run):
    return idle_pct(run)
