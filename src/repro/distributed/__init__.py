from repro.distributed.ctx import (
    MultihostSpec,
    fetch_global,
    initialize_multihost,
    multihost_env,
    process_count,
    process_index,
)

__all__ = [
    "MultihostSpec",
    "fetch_global",
    "initialize_multihost",
    "multihost_env",
    "process_count",
    "process_index",
]
