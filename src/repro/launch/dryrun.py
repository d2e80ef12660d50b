import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and record memory / cost / collective statistics.

The two lines above MUST stay the first statements of this module: jax locks
the device count at first backend init, and the dry-run needs 512 host
devices. Nothing else in the repo sets this flag.

Usage:
  python -m repro.launch.dryrun --arch phi3-medium-14b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all --out benchmarks/results/dryrun.jsonl
      (spawns one subprocess per cell so failures are isolated)
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time


def _lower_compile(cfg, shape, mesh, rules):
    import jax

    from repro.launch.steps import make_step

    fn, args, donate = make_step(cfg, shape, mesh, rules)
    with mesh:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    return compiled


def _probe_costs(cfg, shape, mesh, rules, sh):
    """Loop-exact per-device costs via two small fully-unrolled lowers.

    XLA's cost_analysis counts while-loop bodies ONCE (verified empirically),
    so the scanned-layer stack is undercounted by ~n_super. We lower two
    unrolled probes with 1 and 2 pattern periods and fit
    cost = fixed + per_period * n_super (exact for homogeneous stacks).
    """
    from repro.launch.hlo_stats import collective_bytes
    from repro.models.transformer import plan

    pl = plan(cfg)
    base = len(pl.head) + (cfg.n_layers
                           - len(pl.head) - pl.n_super * max(1, len(pl.pattern)))
    p = max(1, len(pl.pattern))
    probe_chunk = max(1024, sh.seq_len // 8 if sh.kind != "decode" else 1024)

    # SSD probes: cap the number of unrolled chunks at 16 (the within-chunk
    # decay terms scale with Q, inflating those ~5%-of-layer terms). Keeps
    # probe HLOs compilable in minutes.
    ssm = cfg.ssm
    if ssm is not None and sh.kind != "decode" and sh.seq_len // ssm.chunk > 16:
        ssm = dataclasses.replace(ssm, chunk=sh.seq_len // 16)

    results = []
    for k in (1, 2):
        pcfg = dataclasses.replace(
            cfg, n_layers=base + k * p, scan_layers=False, unroll_loops=True,
            attn_chunk=probe_chunk, ssm=ssm,
        )
        compiled = _lower_compile(pcfg, shape, mesh, rules)
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
        results.append({
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll,
        })
    c1, c2 = results
    n_super = pl.n_super if pl.n_super else (cfg.n_layers - base) // p

    def extrapolate(v1, v2):
        slope = max(0.0, v2 - v1)
        fixed = max(0.0, v1 - slope)
        return fixed + slope * n_super

    kinds = set(c1["coll"]) | set(c2["coll"])
    coll = {k: extrapolate(float(c1["coll"].get(k, 0)), float(c2["coll"].get(k, 0)))
            for k in kinds}
    return {
        "flops": extrapolate(c1["flops"], c2["flops"]),
        "bytes": extrapolate(c1["bytes"], c2["bytes"]),
        "coll": coll,
        "probe_chunk": probe_chunk,
    }


def run_cell(arch: str, shape: str, multi_pod: bool, overrides: dict | None = None):
    import jax

    from repro.configs import get_config, shape_applicable
    from repro.configs.base import LM_SHAPES
    from repro.distributed.sharding import default_rules
    from repro.launch.hlo_stats import collective_bytes, roofline_terms
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import make_step

    ok, why = shape_applicable(arch, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(multi_pod=multi_pod)
    sh = LM_SHAPES[shape]

    t0 = time.time()
    fn, args, donate = make_step(cfg, shape, mesh, rules)
    with mesh:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll_raw = collective_bytes(compiled.as_text())

    probe = _probe_costs(cfg, shape, mesh, rules, sh)
    coll = probe["coll"]

    n_chips = mesh.devices.size
    flops = probe["flops"]
    bytes_accessed = probe["bytes"]
    coll_total = float(sum(coll.values()))

    n_active = cfg.active_param_count()
    tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    mult = 6 if sh.kind == "train" else 2
    model_flops_total = mult * n_active * tokens
    model_flops_per_chip = model_flops_total / n_chips

    rec = {
        "arch": arch,
        "shape": shape,
        "multi_pod": multi_pod,
        "status": "ok",
        "n_chips": int(n_chips),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_device_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + mem.temp_size_in_bytes,
        },
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll,
        "collective_total_bytes": coll_total,
        "raw_loopcounted": {  # uncorrected cost_analysis of the real cell
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll_raw,
        },
        "probe_attn_chunk": probe["probe_chunk"],
        "model_flops_total": model_flops_total,
        "useful_flops_fraction": model_flops_per_chip / flops if flops else None,
        "params_total": cfg.param_count(),
        "params_active": n_active,
    }
    rec.update(roofline_terms(flops, bytes_accessed, coll_total))
    return rec


def run_apriori_cell(multi_pod: bool, *, shard_candidates: bool = True,
                     bitmap_dtype: str = "uint8", store: str = "bitmap",
                     n: int = 2**27, f: int = 4096, c: int = 131_072, k: int = 3):
    """The paper's own workload at production scale: one support-counting job
    (the K-ItemsetMapper + combiner + reducer) for a web-scale transaction DB.

    Baseline faithful translation replicates candidates to every mapper (the
    Hadoop distributed-cache pattern: shard_candidates=False) and streams the
    bf16 bitmap; the optimized variants shard candidates over the model axis
    (2-D decomposition) and keep the bitmap uint8 in HBM.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.hlo_stats import collective_bytes, roofline_terms
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = ("pod", "data") if multi_pod else ("data",)
    cand_spec = P("model", None) if shard_candidates else P(None, None)
    kvec_spec = P("model") if shard_candidates else P(None)
    dt = jnp.uint8 if bitmap_dtype == "uint8" else jnp.bfloat16

    bitmap = jax.ShapeDtypeStruct((n, f), dt,
                                  sharding=NamedSharding(mesh, P(dp, None)))
    if store == "bitmap":
        khot = jax.ShapeDtypeStruct((c, f), jnp.bfloat16,
                                    sharding=NamedSharding(mesh, cand_spec))
        kvec = jax.ShapeDtypeStruct((c,), jnp.int32,
                                    sharding=NamedSharding(mesh, kvec_spec))

        def count_step(bitmap, khot, kvec):
            dots = jnp.dot(bitmap.astype(jnp.bfloat16), khot.T,
                           preferred_element_type=jnp.float32)  # (N,C) MXU
            matched = dots == kvec[None].astype(jnp.float32)
            return jnp.sum(matched.astype(jnp.int32), axis=0)  # combiner+reduce

        args = (bitmap, khot, kvec)
    else:  # perfect_hash: k gathers per candidate (the hash-table trie)
        cand = jax.ShapeDtypeStruct(
            (c, k), jnp.int32, sharding=NamedSharding(mesh, cand_spec))

        def count_step(bitmap, cand):
            matched = bitmap[:, cand[:, 0]]
            for level in range(1, cand.shape[1]):
                matched = matched & bitmap[:, cand[:, level]]
            return jnp.sum(matched.astype(jnp.int32), axis=0)

        args = (bitmap, cand)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            count_step,
            out_shardings=NamedSharding(mesh, kvec_spec),
        ).lower(*args)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    n_chips = mesh.devices.size
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    model_flops_total = 2.0 * n * f * c  # the counting matmul itself
    rec = {
        "arch": "apriori-count-step",
        "shape": f"{store}_N{n}_F{f}_C{c}"
                 f"{'_candshard' if shard_candidates else '_candrep'}"
                 f"_{bitmap_dtype}",
        "multi_pod": multi_pod,
        "status": "ok",
        "n_chips": int(n_chips),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_device_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + mem.temp_size_in_bytes,
        },
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll,
        "collective_total_bytes": float(sum(coll.values())),
        "model_flops_total": model_flops_total,
        "useful_flops_fraction": (model_flops_total / n_chips) / flops if flops else None,
    }
    rec.update(roofline_terms(flops, bytes_accessed, float(sum(coll.values()))))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--apriori", action="store_true",
                    help="run the Apriori count-step cells (baseline + variants)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    if args.apriori:
        recs = []
        for mp in (False, True):
            recs.append(run_apriori_cell(mp, shard_candidates=False,
                                         bitmap_dtype="bfloat16"))
            recs.append(run_apriori_cell(mp, shard_candidates=True,
                                         bitmap_dtype="bfloat16"))
            recs.append(run_apriori_cell(mp, shard_candidates=True,
                                         bitmap_dtype="uint8"))
            recs.append(run_apriori_cell(mp, shard_candidates=True,
                                         bitmap_dtype="uint8",
                                         store="perfect_hash"))
        for r in recs:
            print(json.dumps(r))
        if args.out:
            with open(args.out, "a") as fh:
                for r in recs:
                    fh.write(json.dumps(r) + "\n")
        return

    if args.all:
        from repro.configs import cells  # safe: subprocesses own jax init

        out = args.out or "benchmarks/results/dryrun.jsonl"
        os.makedirs(os.path.dirname(out), exist_ok=True)
        done = set()
        if os.path.exists(out):
            with open(out) as f:
                for line in f:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["multi_pod"]))
        todo = []
        for arch, shape, ok, why in cells(include_skipped=True):
            for mp in (False, True):
                if (arch, shape, mp) in done:
                    continue
                todo.append((arch, shape, mp, ok, why))
        for i, (arch, shape, mp, ok, why) in enumerate(todo):
            label = f"[{i + 1}/{len(todo)}] {arch} × {shape} {'pod2' if mp else 'pod1'}"
            if not ok:
                rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "skipped", "reason": why}
                print(f"{label}: SKIP ({why})", flush=True)
            else:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape]
                if mp:
                    cmd.append("--multi-pod")
                t0 = time.time()
                proc = subprocess.run(
                    cmd, capture_output=True, text=True, timeout=args.timeout)
                last = proc.stdout.strip().splitlines()
                if proc.returncode == 0 and last:
                    rec = json.loads(last[-1])
                    print(f"{label}: ok compile={rec['compile_s']}s "
                          f"bottleneck={rec.get('bottleneck')}", flush=True)
                else:
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error",
                           "error": (proc.stderr or "")[-2000:]}
                    print(f"{label}: ERROR ({time.time()-t0:.0f}s)", flush=True)
            with open(out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        return

    rec = run_cell(args.arch, args.shape, args.multi_pod)
    if rec["status"] == "ok":
        print(f"# memory_analysis: {rec['memory']}", file=sys.stderr)
        print(f"# cost_analysis: flops={rec['flops_per_device']:.3e} "
              f"bytes={rec['bytes_per_device']:.3e}", file=sys.stderr)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
