"""reduce_mb.batch: megabytes of partial-count vector each chip adds into
the psums of one mine over the ``data`` mesh (the data-mesh reduce,
``core/runtime/engine.py``: the reducer of Job1 and of every count wave),
the sum of ``JobProfile.reduce_bytes`` over a mine's levels / 1e6,
averaged over the window's mines.  None where no level counted over more
than one ``data`` shard: on one chip, or where ``JobProfile`` has no
``data_shards``.  Moves ``mine_s``."""

MOVES = "mine_s"


def read(obs):
    done = [m for m in obs.mines
            if any(lv.get("data_shards", 1) > 1 for lv in m.levels)]
    if not done:
        return None
    return sum(lv["reduce_bytes"] for m in done
               for lv in m.levels) / len(done) / 1e6
