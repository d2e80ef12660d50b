"""device_idle.batch: percent of the traced stretch of the window (two
whole mines) in which no operation ran on the device, the mean over the
chips.  Moves ``mine_s``."""

MOVES = "mine_s"


def read(obs):
    if obs.trace is None:
        return None
    return 100.0 * obs.trace.idle_share
