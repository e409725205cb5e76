"""Device milliseconds a fleet step spends evaluating the loss of every
walker's model and of their average: the device time of the program's
``fleet_loss_eval`` scope in the traced window (``chipbench.scopes``),
over the fleet steps traced."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "fleet_loss_eval")
