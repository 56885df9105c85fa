"""95th percentile, over every wave in the window, of the wall time from
handing the wave to ``StreamingEngine.absorb`` until its refreshed W is
ready."""
import numpy as np


def read(ctx):
    lat = [u.latency_s for u in ctx.units if u.latency_s is not None]
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
