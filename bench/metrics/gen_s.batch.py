"""gen_s.batch: seconds of host candidate generation per mine (the host
SPC loop, ``core/runtime/strategies.py``), the sum of ``gen_seconds`` over
a mine's levels, averaged over the window's mines.  Moves ``mine_s``."""

MOVES = "mine_s"


def read(obs):
    done = [m for m in obs.mines if m.levels]
    if not done:
        return None
    return sum(lv["gen_seconds"] for m in done for lv in m.levels) / len(done)
