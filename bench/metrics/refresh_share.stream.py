"""refresh_share.stream: percent of the window's exact queries that re-mined
the window before answering (``ServeResult.refreshed``, the service
lattice in ``serve/engine.py:query``).  Moves ``query_p90_s``."""

MOVES = "query_p90_s"


def read(obs):
    done = [y for y in obs.cycles if y.error is None]
    if not done:
        return None
    return 100.0 * sum(y.refreshed for y in done) / len(done)
