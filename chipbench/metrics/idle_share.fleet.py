"""Share of the traced window of the fleet calls in which no operation ran
on the device: 1 - (union of the device's operation intervals) / window."""


def read(ctx):
    summary = ctx["summary"]
    if summary is None or summary.window_s <= 0:
        return None
    return 100.0 * summary.idle_share
