"""Multi-host cluster launch: one binary everywhere, env-selected roles.

The maxtext ``128vm.sh`` idiom: every host runs the *same* command line and
learns its role purely from environment variables — ``REPRO_COORDINATOR`` /
``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` (see ``distributed.ctx``).
This module supplies both halves of that idiom for a single machine: a CPU
cluster rehearsal, the CI substitute for a real pod.  N worker *processes*
run on the CPU backend (``JAX_PLATFORMS=cpu``), each with forced host
devices, with gloo collectives between them; none of them touches an
accelerator, so a chip stays with the one process that owns it.

``worker_env`` / ``launch_cluster``
    Spawn ``num_processes`` copies of an argv with the env trio set and
    supervise them.  The supervisor is the failure detector: the moment any
    worker exits nonzero it kills the rest (their collectives are hung on
    the dead peer — exactly the real-cluster symptom) and raises
    ``ClusterFailure``.

``python -m repro.launch.multihost``
    A process-spanning mining job with elastic recovery.  The parent
    invocation (env trio unset) supervises; each child (trio set)
    initializes ``jax.distributed``, builds the data mesh over the *global*
    device count, and mines with per-level checkpoints into a shared
    directory — process 0 writes, everyone restores.  ``--kill-k`` arms a
    ``faults.process_exit`` plan so a chosen worker genuinely dies
    (``os._exit(137)``) at level-k dispatch; the supervisor then relaunches
    a cluster one process smaller *without* the fault, which resumes from
    the latest checkpoint — completed levels are never re-counted, and the
    result is bit-identical to an unfailed run (counts are mesh- and
    process-count-independent).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import tempfile
import time
from typing import List, Optional, Sequence, Tuple

_FORCE_DEVICES_RE = re.compile(
    r"--xla_force_host_platform_device_count=\d+\s*")


class ClusterFailure(RuntimeError):
    """A worker died; carries who and how (137 == SIGKILL/os._exit(137))."""

    def __init__(self, process_id: int, returncode: int) -> None:
        super().__init__(
            f"worker process {process_id} exited with code {returncode}")
        self.process_id = process_id
        self.returncode = returncode


def find_free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker_env(coordinator: str, num_processes: int, process_id: int,
               local_devices: int = 1, base=None) -> dict:
    """The env one worker launches with: the multihost trio, the CPU
    backend, and a forced per-process host device count (replacing any
    inherited force flag, so a CI job already running under forced devices
    spawns clean workers)."""
    env = dict(os.environ if base is None else base)
    env["REPRO_COORDINATOR"] = coordinator
    env["REPRO_NUM_PROCESSES"] = str(num_processes)
    env["REPRO_PROCESS_ID"] = str(process_id)
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_PLATFORMS"] = "cpu"  # a rehearsal cluster: never the chip
    if local_devices:
        flags = _FORCE_DEVICES_RE.sub("", env.get("XLA_FLAGS", "")).strip()
        force = f"--xla_force_host_platform_device_count={local_devices}"
        env["XLA_FLAGS"] = (flags + " " + force).strip()
    return env


def launch_cluster(argv: Sequence[str], num_processes: int,
                   local_devices: int = 1, coordinator: Optional[str] = None,
                   base_env=None, popen=None, poll_interval: float = 0.05,
                   timeout: Optional[float] = None) -> str:
    """Spawn ``num_processes`` copies of ``argv`` (same command, different
    env — the SPMD launch) and supervise until all exit cleanly.

    The first worker to exit nonzero fails the cluster: the survivors are
    killed (they are blocked in collectives on the dead peer) and
    ``ClusterFailure`` is raised.  ``popen`` is injectable for tests.
    Returns the coordinator address on success.
    """
    popen = popen or subprocess.Popen
    coordinator = coordinator or f"127.0.0.1:{find_free_port()}"
    procs = [
        popen(list(argv), env=worker_env(coordinator, num_processes, pid,
                                         local_devices, base_env))
        for pid in range(num_processes)
    ]
    t0 = time.monotonic()
    try:
        while True:
            codes = [p.poll() for p in procs]
            dead = [(pid, rc) for pid, rc in enumerate(codes)
                    if rc is not None and rc != 0]
            if dead:
                raise ClusterFailure(*dead[0])
            if all(rc == 0 for rc in codes):
                return coordinator
            if timeout is not None and time.monotonic() - t0 > timeout:
                raise TimeoutError(
                    f"cluster did not finish within {timeout}s")
            time.sleep(poll_interval)
    finally:
        # On success every poll() is 0 and this is a no-op; on failure it is
        # the supervisor's kill of the hung survivors.
        for p in procs:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass


# -- the mining job (worker role) --------------------------------------------

def _worker_main(args) -> int:
    from repro.distributed import ctx

    ctx.initialize_multihost()  # before anything touches jax device state
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.core.miner import FrequentItemsetMiner
    from repro.core.runtime import ShardedRunner
    from repro.core.runtime import faults as F
    from repro.core.runtime.faults import FaultPlan
    from repro.data import get_dataset
    from repro.distributed import checkpoint as ckpt
    from repro.launch.mesh import make_data_mesh

    db = get_dataset(args.dataset, scale=args.scale, seed=args.seed)
    restored = None
    if args.checkpoint_dir and os.path.isdir(args.checkpoint_dir):
        restored = ckpt.latest_step(args.checkpoint_dir)
    plan = None
    if args.kill_k is not None:
        plan = FaultPlan(F.process_exit(k=args.kill_k,
                                        process=args.kill_process))
    runner = ShardedRunner(store=args.store, mesh=make_data_mesh(),
                           fault_plan=plan)
    miner = FrequentItemsetMiner(min_support=args.min_support,
                                 max_k=args.max_k, runner=runner,
                                 checkpoint_dir=args.checkpoint_dir)
    res = miner.mine(db)
    if jax.process_index() == 0 and args.out:
        payload = {
            "itemsets": sorted([list(s), int(c)]
                               for s, c in res.itemsets.items()),
            "n_transactions": res.n_transactions,
            "min_count": res.min_count,
            "processes": int(jax.process_count()),
            "devices": int(jax.device_count()),
            # The step this (final, successful) cluster resumed from — None
            # on a clean first run, >= 2 after a mid-wave relaunch.
            "restored_step": restored,
            # Level-counting profile rows (k >= 2), restored ones included:
            # on a resumed run this still equals the clean run's ledger —
            # no level double-counted or skipped.
            "counting_jobs": sum(1 for p in res.levels if p.k >= 2),
        }
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, args.out)
    return 0


# -- the supervisor (parent role) --------------------------------------------

def _build_argv(args, include_kill: bool) -> List[str]:
    argv = [sys.executable, "-m", "repro.launch.multihost",
            "--dataset", args.dataset, "--scale", str(args.scale),
            "--seed", str(args.seed),
            "--min-support", str(args.min_support),
            "--store", args.store, "--max-k", str(args.max_k),
            "--processes", str(args.processes),
            "--local-devices", str(args.local_devices),
            "--checkpoint-dir", args.checkpoint_dir,
            "--out", args.out]
    if include_kill and args.kill_k is not None:
        # Faults are one-shot, like the real failure: relaunches run clean.
        argv += ["--kill-k", str(args.kill_k),
                 "--kill-process", str(args.kill_process)]
    return argv


def supervise(args) -> dict:
    """Launch the cluster; on a worker death, relaunch one process smaller
    from the shared checkpoint dir (up to ``--elastic`` times)."""
    if not args.checkpoint_dir:
        args.checkpoint_dir = tempfile.mkdtemp(prefix="repro_multihost_")
    if not args.out:
        args.out = os.path.join(args.checkpoint_dir, "result.json")
    n = args.processes
    relaunches = 0
    failures: List[Tuple[int, int]] = []
    while True:
        try:
            launch_cluster(_build_argv(args, include_kill=relaunches == 0),
                           n, local_devices=args.local_devices,
                           timeout=args.timeout)
            break
        except ClusterFailure as f:
            failures.append((f.process_id, f.returncode))
            relaunches += 1
            if relaunches > args.elastic:
                raise
            n = max(1, n - 1)
            print(f"[multihost] worker {f.process_id} died "
                  f"(rc={f.returncode}); relaunching {n} process(es) from "
                  f"{args.checkpoint_dir}", flush=True)
    with open(args.out) as f:
        result = json.load(f)
    return {"result": result, "relaunches": relaunches,
            "failures": failures, "final_processes": n}


def _parse(argv=None):
    ap = argparse.ArgumentParser(
        prog="repro.launch.multihost",
        description="process-spanning mining job with elastic recovery "
                    "(parent supervises; REPRO_* env makes it a worker)")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=1,
                    help="forced host devices per process")
    ap.add_argument("--dataset", default="T10I4D100K")
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-support", type=float, default=0.05)
    ap.add_argument("--store", default="perfect_hash")
    ap.add_argument("--max-k", type=int, default=6)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--out", default=None,
                    help="result JSON (written by worker process 0)")
    ap.add_argument("--kill-k", type=int, default=None,
                    help="kill a worker at level-k dispatch (fault demo)")
    ap.add_argument("--kill-process", type=int, default=1)
    ap.add_argument("--elastic", type=int, default=1,
                    help="max cluster relaunches after a worker death")
    ap.add_argument("--timeout", type=float, default=600.0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    from repro.distributed.ctx import multihost_env

    if multihost_env() is not None:
        return _worker_main(args)
    summary = supervise(args)
    print("MULTIHOST_OK " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
