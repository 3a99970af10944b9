"""Model FLOP/s of the traced window over the chip's bf16 peak, in percent,
in the latent attention and held-expert cell: the operations the requests
of the traced waves need (``roofline_mla_moe.serve_flops``: weights times
tokens, each token's held experts at 6 x 8 / 64 evaluations, attention's
score and value products not counted) over the traced window's seconds."""

from bench import roofline_mla_moe


def read(run):
    tr = run.trace_result
    if tr is None or not run.traced["items"]:
        return None
    cfg = run.cell.config
    flops = sum(roofline_mla_moe.serve_flops(cfg, it.sizes["prompt_len"],
                                             it.sizes["output_len"])
                for w in run.traced["items"] for it in w["items"])
    return 100.0 * flops / (tr["window_s"]
                            * run.peaks()["bf16_flops_per_s"])
