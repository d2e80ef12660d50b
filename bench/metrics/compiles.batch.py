"""compiles.batch: programs JAX builds or loads from the persistent cache
per mine in the window (``jax.monitoring`` backend-compile events).  A new
miner builds a new engine whose jitted functions are traced, lowered and
loaded again.  Moves ``mine_s``."""

MOVES = "mine_s"


def read(obs):
    if not obs.mines:
        return None
    return sum(m.compiles.programs for m in obs.mines) / len(obs.mines)
