"""Share of its roofline that the decode program reaches in the latent
attention and held-expert cell, in percent: for each decode step of the
traced waves, the bytes ``roofline_mla_moe.decode_step_bytes`` counts
(every weight once, each held expert at the chance that one of the wave's
rows routes to it, the live latent cache) at the chip's HBM bandwidth,
over the device time of the decode program. Memory bound: a step of 16
rows does about 16 operations per weight byte."""

from bench import roofline_mla_moe
from bench.readers import device_time


def is_decode(name, module):
    return "_decode_fn" in name


def read(run):
    secs = device_time(run, is_decode, modules=True)
    if not secs:
        return None
    cfg, sv = run.cell.config, run.cell.config["serve"]
    need = sum(roofline_mla_moe.decode_step_bytes(cfg, sv["batch_size"],
                                                  w["prompt_len"] + t)
               for w in run.traced["items"]
               for t in range(1, sv["max_new_tokens"]))
    return 100.0 * need / run.peaks()["hbm_bytes_per_s"] / secs
