"""One run of one cell: set-up, the measured window, the check, the result.

``run`` is the whole benchmark; ``bench/run.py`` calls it with the command
line, and the tests call it at a tiny size on the CPU (``require_tpu`` off
and ``shrink`` set), which is the rehearsal.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the driver of its ``kind`` in
``bench/kinds/<kind>.py``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

from bench import check, data, load
from bench.counters import CompileCounter

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Tracer:
    """The profiler around one stretch of the window (``--trace 1``), with
    the host span ``bench.window`` around the same stretch."""

    def __init__(self, enabled: bool) -> None:
        self.enabled = enabled
        self.dir: Optional[tempfile.TemporaryDirectory] = None
        self._span = None
        self.path: Optional[str] = None

    def start(self) -> None:
        if not self.enabled or self.dir is not None:
            return
        import jax

        self.dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir.name, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self) -> None:
        if self._span is None:
            return
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        found = sorted(Path(self.dir.name).rglob("*.xplane.pb"))
        self.path = str(found[-1]) if found else None

    def close(self) -> None:
        if self.dir is not None:
            self.dir.cleanup()


class Run:
    """What a kind and the metric readers share about the run."""

    def __init__(self, seed: int, log) -> None:
        self.seed = seed
        self.log = log
        self.counter = CompileCounter()
        self.tracer = Tracer(False)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def cell(workload: str) -> dict:
    """The cell ``workload`` with its configuration, traffic and metrics."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "workload": w,
        "config": load_json(HERE / "configs" / f"{w['config']}.json"),
        "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return load("metrics", name).read


def driver(name: str):
    """The driver of a traffic kind: the ``Kind`` of
    ``bench/kinds/<name>.py``."""
    return load("kinds", name).Kind


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoDevice(f"no TPU: JAX platform {info['platform']}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devs)}")
    return info


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, require_tpu: bool = True,
        shrink: Optional[dict] = None, cache_dir: Path = data.CACHE,
        compile_cache: bool = True, out=None, err=None) -> dict:
    """One run; prints progress and the checks on ``err`` and the result
    line last on ``out``, and returns the result.  ``shrink`` overrides
    keys of the configuration's ``generator`` and of the traffic (the CPU
    rehearsal's tiny sizes); ``compile_cache`` off leaves JAX's persistent
    compilation cache as it is (the rehearsal's).  Raises ``NoDevice``
    before any work when JAX finds no TPU (with ``require_tpu``) or too
    few chips."""
    t_start = time.perf_counter() if t_start is None else t_start
    out = out or sys.stdout
    err = err or sys.stderr

    def log(msg: str) -> None:
        print(msg, file=err, flush=True)

    c = cell(workload)
    config, traffic = c["config"], dict(c["traffic"])
    if shrink:
        config = dict(config, generator=dict(config["generator"],
                                             **shrink.get("generator", {})))
        traffic.update(shrink.get("traffic", {}))
    chips = c["workload"]["chips"]
    dev = device_info(chips, require_tpu)
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"devices={dev['count']} workload={workload} seed={seed}")

    import jax

    sys.path.insert(0, str(ROOT / "src"))
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    r = Run(seed, log)
    r.tracer = Tracer(trace)
    base = data.base_dataset(config["name"], config["generator"], cache_dir)
    # A mix with a fixed arrival order (``order_seed``) keeps the rows in
    # the base's order; the seed then relabels the items alone.
    mat = data.seeded(base, seed, reorder="order_seed" not in traffic)
    log(f"dataset {config['name']}: {mat.shape[0]} baskets, width "
        f"{mat.shape[1]}, {len(set(mat[mat >= 0].tolist()))} items")
    kind = driver(traffic["kind"])(r, config, traffic, mat)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s} ({r.counter.now()})")

    window_s = kind.window(seconds)
    for line in kind.summary():
        log(line)
    device = dict(dev, memory_peak_bytes=memory_peak(chips))
    log(f"memory_peak_bytes={device['memory_peak_bytes']} window_s={window_s}")
    kind.free()

    summary = None
    if trace and r.tracer.path:
        from bench import trace as trace_mod

        try:
            summary = trace_mod.reduce(r.tracer.path)
        except ValueError as e:  # no device plane: the CPU rehearsal
            log(f"trace: {e}")
    r.tracer.close()

    numbers, checked, failed = kind.check()
    correct = check.passes(numbers) and checked >= 1 and failed == 0
    checks = check.as_checks(numbers, checked)

    metrics: Dict[str, dict] = {}
    if trace:
        obs = Observations(kind=kind, window_s=window_s, trace=summary,
                           device=dev, chips=chips)
        for m in c["per_layer"]:
            value = reader(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        e2e = dict(kind.end_to_end(window_s), setup_s=setup_s)
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": kind.attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps],
        }
    result["checks"] = checks
    for name, v in checks.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), file=out, flush=True)
    return result


class Observations:
    """What a per-layer metric's reader reads: the kind's records (its
    ``mines`` or ``cycles``), the window, and the trace summary."""

    def __init__(self, kind, window_s: float, trace, device: dict,
                 chips: int) -> None:
        self.kind = kind
        self.window_s = window_s
        self.trace = trace
        self.device = device
        self.chips = chips

    @property
    def mines(self) -> List:
        return getattr(self.kind, "mines", [])

    @property
    def traced_mines(self) -> List:
        return self.mines[:self.kind.trace_mines]

    @property
    def cycles(self) -> List:
        return getattr(self.kind, "cycles", [])

