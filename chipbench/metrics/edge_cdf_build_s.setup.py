"""Seconds of the run's set-up spent building the walk engine's flat
per-edge CDF: the program's ``edge_cdf_build`` span, as the engine module
totals it (``repro.core.engine.span_seconds``).  The window builds no
engine, so the total is the set-up's."""
import sys


def read(ctx):
    engine = sys.modules.get("repro.core.engine")
    seconds = getattr(engine, "span_seconds", {}).get("edge_cdf_build")
    return seconds or None
