"""compiles.stream: programs JAX builds or loads from the persistent cache
per 100 arrival batches in the window (``jax.monitoring`` backend-compile
events): new candidate shapes after a refresh.  Moves
``stream_baskets_per_s``."""

MOVES = "stream_baskets_per_s"


def read(obs):
    if not obs.cycles:
        return None
    return 100.0 * sum(y.compiles.programs for y in obs.cycles) / len(obs.cycles)
