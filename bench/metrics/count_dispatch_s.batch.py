"""count_dispatch_s.batch: seconds the host spends dispatching the count
waves per mine (wave dispatch, ``core/runtime/engine.py:
count_candidates_async``), the sum of ``encode_seconds`` over the levels
k >= 2, averaged over the window's mines.  At the default queue depth of
1 the dispatch forces the oldest chunk's counts to the host before it
queues the next, so most of the wait on the device is here and not in
``count_wait_s.batch``.  Moves ``mine_s``."""

MOVES = "mine_s"


def read(obs):
    done = [m for m in obs.mines if m.levels]
    if not done:
        return None
    return sum(lv["encode_seconds"] for m in done for lv in m.levels
               if lv["k"] >= 2) / len(done)
