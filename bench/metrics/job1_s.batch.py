"""job1_s.batch: seconds of Job1 (the item histogram,
``core/runtime/engine.py:count_items_device``) per mine, the mean of the
k = 1 ``JobProfile.seconds`` over the window's mines.  Moves ``mine_s``."""

MOVES = "mine_s"


def read(obs):
    vals = [lv["seconds"] for m in obs.mines for lv in m.levels
            if lv["k"] == 1]
    return sum(vals) / len(vals) if vals else None
