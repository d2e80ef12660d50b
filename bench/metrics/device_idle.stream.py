"""device_idle.stream: percent of the traced stretch of the window (about
20 ingest and query cycles) in which no operation ran on the device, the
mean over the chips.  Moves ``stream_baskets_per_s``."""

MOVES = "stream_baskets_per_s"


def read(obs):
    if obs.trace is None:
        return None
    return 100.0 * obs.trace.idle_share
