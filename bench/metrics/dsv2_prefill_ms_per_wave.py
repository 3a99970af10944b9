"""Device milliseconds of the prefill program (``_prefill_fn``) per traced
wave in the latent attention and held-expert cell."""

from bench.readers import device_time


def is_prefill(name, module):
    return "_prefill_fn" in name


def read(run):
    secs = device_time(run, is_prefill, modules=True)
    waves = len(run.traced.get("items", ()))
    return 1e3 * secs / waves if secs and waves else None
