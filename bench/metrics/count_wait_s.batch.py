"""count_wait_s.batch: seconds the host waits on the device's counts per
mine (wave dispatch, ``core/runtime/engine.py:count_candidates_async``),
the sum of ``count_seconds`` over the levels k >= 2, averaged over the
window's mines.  Moves ``mine_s``."""

MOVES = "mine_s"


def read(obs):
    done = [m for m in obs.mines if m.levels]
    if not done:
        return None
    return sum(lv["count_seconds"] for m in done for lv in m.levels
               if lv["k"] >= 2) / len(done)
