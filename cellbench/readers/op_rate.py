"""Operations ``kinds`` completed and verified in the window, per second."""

from .. import stats


def read(cell, kinds):
    done = [o for o in cell.window_ops(*kinds) if o[4]]
    return stats.rate(len(done), cell.seconds) if done else None
