"""count_roofline.batch: percent of the count's roofline reached by the
device programs that count (``core/stores/*.py:count_block`` inside the
programs ``bench/programs.json`` lists).

The least time is worked out from the algorithm's work over the unpadded
shapes (``bench/roofline.py``) for every counting level of the traced
mines, and divided by those programs' device time in the trace.  Moves
``mine_s``."""

MOVES = "mine_s"


def read(obs):
    from bench import roofline, trace

    if obs.trace is None or not obs.traced_mines:
        return None
    device_s = trace.matching_seconds(obs.trace, roofline.count_patterns())
    if device_s <= 0:
        return None
    peak = roofline.peaks(obs.device["kind"])
    n = obs.kind.data.shape[0]
    least = 0.0
    for m in obs.traced_mines:
        f = m.levels[0]["n_frequent"]
        levels = [(lv["k"], lv["n_candidates"]) for lv in m.levels
                  if lv["k"] >= 2]
        t, bound = roofline.least_time(n, f, levels, peak, chips=obs.chips)
        least += t
    obs.kind.run.log(f"count_roofline.batch: least {least} s ({bound} "
                     f"bound) over {device_s} s of counting programs")
    return 100.0 * least / device_s
