"""Device milliseconds a fleet step spends in the walk transition: the
device time of the program's ``walk_transition`` scope in the traced
window (``chipbench.scopes``), over the fleet steps traced."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "walk_transition")
