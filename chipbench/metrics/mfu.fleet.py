"""The whole fleet step's share of the chip's bf16 peak: the operations
that the traced steps require (``counts.fleet_step_flops``), over the
traced window, over the peak."""


def read(ctx):
    counts, summary, peak = ctx["counts"], ctx["summary"], ctx["peak"]
    if "fleet_flops" not in counts or summary is None or summary.window_s <= 0:
        return None
    return 100.0 * counts["fleet_flops"] / summary.window_s / peak["bf16_flops_per_s"]
