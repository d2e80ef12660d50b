"""ingest_ms.stream: median milliseconds of one ingest call as the service
reports it (``IngestReport.seconds``, ``serve/engine.py:ingest``), over the
window's cycles.  Moves ``stream_baskets_per_s``."""

import statistics

MOVES = "stream_baskets_per_s"


def read(obs):
    vals = [y.report_s for y in obs.cycles if y.error is None]
    return 1000.0 * statistics.median(vals) if vals else None
