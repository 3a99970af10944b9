"""Share of its roofline that the decode program reaches, in percent: for
each decode step of the traced waves, every weight once and the live
cache (``roofline.decode_step_bytes``) at the chip's HBM bandwidth, over
the device time of the decode program. Memory bound: a step of 16 rows
does about 16 operations per weight byte."""

from bench import roofline
from bench.readers import device_time


def is_decode(name, module):
    return "_decode_fn" in name


def read(run):
    secs = device_time(run, is_decode, modules=True)
    if not secs:
        return None
    cfg, sv = run.cell.config, run.cell.config["serve"]
    need = sum(roofline.decode_step_bytes(cfg, sv["batch_size"],
                                          w["prompt_len"] + t)
               for w in run.traced["items"]
               for t in range(1, sv["max_new_tokens"]))
    return 100.0 * need / run.peaks()["hbm_bytes_per_s"] / secs
