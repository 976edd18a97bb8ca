"""Bytes of the operations ``kinds`` completed in the window, per second.

``over`` = "window": divided by the window. ``over`` = "last_completion":
divided by the seconds from the window's start to the last such
operation that completed inside it — whole units of work over the time
they took, so a unit cut off by the window's end costs no resolution."""

from .. import stats


def read(cell, kinds, scale=1e6, over="window"):
    done = [o for o in cell.window_ops(*kinds) if o[4]]
    if not done:
        return None
    seconds = (cell.seconds if over == "window"
               else max(o[2] for o in done) - cell.t0)
    r = stats.rate(sum(o[3] for o in done), seconds)
    return None if r is None else r / scale
