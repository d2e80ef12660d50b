"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  BENCH_SCALE=0.02 python -m benchmarks.run fig      # subset by name
  python -m benchmarks.run --tables [BENCH_paper.json]   # re-render pivots

Prints ``name,us_per_call,derived`` CSV and persists each suite's rows as
machine-readable ``BENCH_<suite>.json`` at the repo root (fields: name, us,
meta) so the perf trajectory is tracked across PRs. Roofline numbers live in
benchmarks/results/dryrun.jsonl (see repro.launch.dryrun) and are rendered by
benchmarks/roofline_report.py.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def persist(suite: str, lines: list) -> str:
    """Write one suite's CSV rows as BENCH_<suite>.json next to ROADMAP.md.

    The canonical cross-PR trajectory file is only written at the default
    BENCH_SCALE; smoke runs at other scales go to a scale-suffixed file so
    they never clobber the tracked numbers. The scale is recorded either way.
    """
    from benchmarks.common import DEFAULT_SCALE, SCALE

    rows = []
    for line in lines:
        if line.startswith("#"):
            continue
        name, us, meta = line.split(",", 2)
        rows.append({"name": name, "us": float(us), "meta": meta})
    stem = f"BENCH_{suite}" if SCALE == DEFAULT_SCALE else f"BENCH_{suite}@{SCALE:g}"
    path = os.path.join(REPO_ROOT, f"{stem}.json")
    with open(path, "w") as f:
        json.dump({"suite": suite, "scale": SCALE, "rows": rows}, f, indent=1)
    return path


def render_profile_table(lines: list) -> list:
    """Pivot ``runtime/profile/<backend>/k<k>`` rows into one cross-backend
    comparison table (backends x k, cell = parallel ms / measured wall ms).
    Returned as '#'-prefixed lines: printed for humans, skipped by persist().
    """
    cells, ks = {}, set()
    for line in lines:
        if line.startswith("#") or not line.startswith("runtime/profile/"):
            continue
        name, us, meta = line.split(",", 2)
        backend, k = name[len("runtime/profile/"):].rsplit("/k", 1)
        wall = dict(p.split("=", 1) for p in meta.split(";") if "=" in p).get(
            "wall_ms", "")
        cells[(backend, int(k))] = f"{float(us) / 1e3:.0f}/{float(wall):.0f}"
        ks.add(int(k))
    if not cells:
        return []
    ks = sorted(ks)
    backends = sorted({b for b, _ in cells})
    width = max(len(b) for b in backends)
    out = ["# cross-backend JobProfile table: parallel ms (model) / "
           "measured wall ms, per level k",
           "# " + "backend".ljust(width) + " | " +
           " | ".join(f"k={k:<9}" for k in ks)]
    for b in backends:
        out.append("# " + b.ljust(width) + " | " + " | ".join(
            f"{cells.get((b, k), '-'):<11}" for k in ks))
    return out


def _parse_paper_rows(rows: list) -> dict:
    """``paper/<ds>/<structure>/<store>/s<sup>/m<m>/<backend>`` rows ->
    {(ds, structure, store, support, mappers, backend): seconds}."""
    cells = {}
    for r in rows:
        name = r["name"]
        if not name.startswith("paper/"):
            continue
        parts = name.split("/")
        if len(parts) != 7:
            continue
        _, ds, structure, store, s, m, backend = parts
        cells[(ds, structure, store, float(s[1:]), int(m[1:]), backend)] = \
            r["us"] / 1e6
    return cells


def render_paper_tables(rows: list) -> list:
    """Pivot paper-grid rows into the paper's two table shapes, as
    '#'-prefixed lines (printed for humans, skipped by persist()):

    1. execution time vs min_support per candidate structure (Fig 2-4) —
       sim rows at the largest swept mapper count, with the measured
       jax/sharded array-store rows alongside;
    2. speedup vs mapper count per structure (Table 2 / Fig 5) at the
       deepest (smallest) swept support.
    """
    cells = _parse_paper_rows(rows)
    if not cells:
        return []
    out = []
    for ds in sorted({k[0] for k in cells}):
        sub = {k: v for k, v in cells.items() if k[0] == ds}
        supports = sorted({k[3] for k in sub}, reverse=True)
        mappers = sorted({k[4] for k in sub})
        structures = sorted({k[1] for k in sub})
        stores = sorted({k[2] for k in sub})
        m_ref, s_ref = mappers[-1], supports[-1]

        # -- table 1: execution time (s) vs min_support ---------------------
        rows1 = [(f"sim/{st}", {s: sub.get((ds, st, stores[0], s, m_ref, "sim"))
                                for s in supports}) for st in structures]
        for backend in ("jax", "sharded"):
            vals = {s: sub.get((ds, structures[0], stores[0], s, m_ref, backend))
                    for s in supports}
            if any(v is not None for v in vals.values()):
                rows1.append((f"{backend}/{stores[0]}", vals))
        width = max(len(label) for label, _ in rows1)
        out.append(f"# [{ds}] execution time (s) vs min_support "
                   f"(mappers={m_ref}):")
        out.append("# " + "backend".ljust(width) + " | " +
                   " | ".join(f"s={s:<7g}" for s in supports))
        for label, vals in rows1:
            out.append("# " + label.ljust(width) + " | " + " | ".join(
                f"{vals[s]:<9.3f}" if vals[s] is not None else "-".ljust(9)
                for s in supports))

        # -- table 2: speedup vs mappers ------------------------------------
        out.append(f"# [{ds}] speedup vs mappers (min_support={s_ref:g}, "
                   "sim parallel-time model):")
        width = max(len(st) for st in structures)
        out.append("# " + "structure".ljust(width) + " | " +
                   " | ".join(f"m={m:<7}" for m in mappers))
        for st in structures:
            base = sub.get((ds, st, stores[0], s_ref, mappers[0], "sim"))
            vals = []
            for m in mappers:
                t = sub.get((ds, st, stores[0], s_ref, m, "sim"))
                vals.append(f"{base / t:<9.2f}" if base and t else "-".ljust(9))
            out.append("# " + st.ljust(width) + " | " + " | ".join(vals))
    return out


def render_tables_from_json(path: str) -> None:
    """Re-render the paper pivot tables from a persisted BENCH_paper.json."""
    with open(path) as f:
        payload = json.load(f)
    lines = render_paper_tables(payload.get("rows", []))
    if not lines:
        raise SystemExit(f"no paper/ rows found in {path}")
    for line in lines:
        print(line)


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--tables":
        render_tables_from_json(sys.argv[2] if len(sys.argv) > 2
                                else os.path.join(REPO_ROOT, "BENCH_paper.json"))
        return

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_iterations,
        bench_mappers,
        bench_min_support,
        bench_outofcore,
        bench_paper,
        bench_runtime,
        bench_serve,
        bench_stores_jax,
        bench_strategies,
    )

    suites = {
        "fig2-4_min_support": bench_min_support.run,
        "table1_iterations": bench_iterations.run,
        "table2_fig5_mappers": bench_mappers.run,
        "stores_jax": bench_stores_jax.run,
        "strategies": bench_strategies.run,
        "runtime": bench_runtime.run,
        # The ladder rows alone (they also ride the full runtime suite) —
        # the quick CI check that fused == host loop and trimming shrinks.
        "level_ladder": bench_runtime.run_level_ladder,
        # Suite mode persists BENCH_paper_smoke.json — the committed
        # BENCH_paper.json parity certificate is written only by the
        # dedicated `benchmarks/bench_paper.py [--quick]` CLI.
        "paper_smoke": bench_paper.run,
        # Streaming service: delta-update ingest vs full-window recount —
        # the serving layer's throughput/latency certificate.
        "serve": bench_serve.run,
        # Out-of-core chunked streaming vs fully-resident ingest — the
        # split-size sweep doubling as a hard parity certificate.
        "outofcore": bench_outofcore.run,
    }
    pattern = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if pattern and pattern not in name:
            continue
        t0 = time.time()
        lines = []
        for line in fn():
            lines.append(line)
            print(line, flush=True)
        for tline in render_profile_table(lines):
            print(tline, flush=True)
        for tline in render_paper_tables(
                [dict(zip(("name", "us", "meta"),
                          (n, float(u), m)))
                 for n, u, m in (l.split(",", 2) for l in lines
                                 if not l.startswith("#"))]):
            print(tline, flush=True)
        path = persist(name, lines)
        print(f"# suite {name} done in {time.time() - t0:.1f}s -> {path}",
              flush=True)


if __name__ == "__main__":
    main()
