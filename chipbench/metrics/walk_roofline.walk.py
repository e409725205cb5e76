"""Share of the walk transition's roofline: the least time to move the
bytes (or do the operations) that the traced calls' MHLJ transitions
require, over the device-busy time of those calls.  The bytes bound it:
the operations are a handful per transition."""


def read(ctx):
    counts, summary, peak = ctx["counts"], ctx["summary"], ctx["peak"]
    if "walk_bytes" not in counts or summary is None or summary.busy_s <= 0:
        return None
    least_s = max(counts["walk_bytes"] / peak["hbm_bytes_per_s"],
                  counts["walk_flops"] / peak["bf16_flops_per_s"])
    return 100.0 * least_s / summary.busy_s
