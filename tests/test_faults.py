"""Fault-tolerant mining runtime: deterministic fault injection, Hadoop-style
task recovery (bounded retry + backoff + speculative execution), crash-safe
self-validating checkpoints, and elastic device-loss recovery.

The single correctness oracle everywhere: a faulted run's itemsets AND
supports must be bit-identical to the fault-free run's."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro
from repro.core import FrequentItemsetMiner
from repro.core.runtime import (
    DeviceLostError,
    FaultPlan,
    FaultSpec,
    JaxRunner,
    JobFailedError,
    RetryPolicy,
    ShardedRunner,
    SimRunner,
)
from repro.core.runtime import faults as F
from repro.core.runtime.faults import MapperCrashError
from repro.data import quest_generator
from repro.distributed import checkpoint as ckpt
from repro.distributed.checkpoint import CheckpointCorruptError, TornWriteError

MIN_SUPPORT = 0.05
FAST_RETRY = RetryPolicy(backoff=0.001)


@pytest.fixture(scope="module")
def db():
    """Mines to k=6: enough levels for multi-snapshot fallback stories."""
    return quest_generator(n_transactions=300, avg_transaction_len=8,
                           n_items=50, n_patterns=30, seed=3)


@pytest.fixture(scope="module")
def clean(db):
    return FrequentItemsetMiner(
        min_support=MIN_SUPPORT, runner=SimRunner(structure="trie")).mine(db)


def _subprocess_env():
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ, PYTHONPATH=src)
    return env


class _JobCountingRunner(SimRunner):
    """SimRunner that counts how many Job1/Job2 executions actually ran —
    the observable difference between a resumed and a from-scratch mine."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.jobs_run = 0

    def job1(self):
        self.jobs_run += 1
        return super().job1()

    def count(self, job):
        self.jobs_run += 1
        return super().count(job)


# -- FaultPlan: deterministic, addressable, consumable ----------------------

def test_fault_plan_addressing_and_consumption():
    plan = FaultPlan(F.crash(k=2, slot=1), F.corrupt(k=3, slot=0, times=2))
    assert plan.mapper_action(k=2, slot=0, attempt=0) is None  # wrong slot
    assert plan.mapper_action(k=2, slot=1, attempt=1) is None  # wrong attempt
    a = plan.mapper_action(k=2, slot=1, attempt=0)
    assert a is not None and a.kind == "crash"
    assert plan.mapper_action(k=2, slot=1, attempt=0) is None  # consumed
    # times=2: fires twice, then never again
    assert plan.mapper_action(k=3, slot=0, attempt=0).kind == "corrupt"
    assert plan.mapper_action(k=3, slot=0, attempt=0).kind == "corrupt"
    assert plan.mapper_action(k=3, slot=0, attempt=0) is None
    assert plan.exhausted
    assert [kind for kind, _ in plan.injected] == ["crash", "corrupt",
                                                   "corrupt"]


def test_fault_plan_wildcards_match_any_address():
    plan = FaultPlan(F.crash(attempt=None, times=3))
    for addr in [(1, 0, 0), (5, 3, 2), (2, 1, 1)]:
        k, slot, attempt = addr
        assert plan.mapper_action(k=k, slot=slot, attempt=attempt) is not None
    assert plan.mapper_action(k=1, slot=0, attempt=0) is None


def test_fault_plan_chaos_is_reproducible():
    a = FaultPlan.chaos(n_faults=4, seed=7)
    b = FaultPlan.chaos(n_faults=4, seed=7)
    assert a.specs == b.specs
    assert FaultPlan.chaos(n_faults=4, seed=8).specs != a.specs
    # every chaos spec carries a precise address — pool scheduling cannot
    # change which task attempt it hits
    assert all(s.k is not None and s.slot is not None and s.attempt == 0
               for s in a.specs)


def test_fault_spec_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("meteor")


def test_checkpoint_action_stages():
    plan = FaultPlan(F.torn_write(step=2, tensor=0), F.kill_commit(step=3),
                     F.bitrot(step=4, tensor=1))
    assert plan.checkpoint_action(step=1, tensor=0, stage="tensor") is None
    assert plan.checkpoint_action(step=2, tensor=0,
                                  stage="tensor").kind == "torn_write"
    assert plan.checkpoint_action(step=3, stage="commit").kind == "kill_commit"
    rot = plan.checkpoint_action(step=4, stage="committed")
    assert rot.kind == "bitrot" and rot.tensor == 1
    with pytest.raises(ValueError):
        plan.checkpoint_action(step=1, stage="meteor")


# -- task recovery: retry parity across executors ---------------------------

@pytest.mark.parametrize("executor", [None, "thread", "process"])
def test_crash_and_corruption_retry_parity(db, clean, executor):
    """Crashed and silently-corrupted mapper attempts are retried; the final
    counts are bit-identical to the fault-free run on every executor."""
    plan = FaultPlan(F.crash(k=2, slot=0), F.corrupt(k=3, slot=1),
                     F.crash(k=1, slot=2))
    with SimRunner(structure="trie", executor=executor, fault_plan=plan,
                   retry=FAST_RETRY) as runner:
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                                   runner=runner).mine(db)
    assert res.itemsets == clean.itemsets
    assert len(plan.injected) == 3
    assert sum(p.retries for p in res.levels) == 3
    assert sum(p.backoff_seconds for p in res.levels) > 0


@pytest.mark.parametrize("strategy", ["fpc", "dpc"])
def test_retry_parity_through_combined_strategies(db, clean, strategy):
    """Combined (multi-wave) jobs aggregate retry telemetry and stay exact."""
    plan = FaultPlan(F.crash(k=2, slot=0), F.corrupt(k=3, slot=0))
    with SimRunner(structure="hash_tree", executor="thread", fault_plan=plan,
                   retry=FAST_RETRY) as runner:
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT, strategy=strategy,
                                   runner=runner).mine(db)
    assert res.itemsets == clean.itemsets
    assert sum(p.retries for p in res.levels) == len(plan.injected) >= 1


def test_chaos_plan_parity(db, clean):
    """A randomized (but seeded) chaos schedule never changes results."""
    plan = FaultPlan.chaos(n_faults=5, seed=11, max_k=4)
    with SimRunner(structure="trie", executor="thread", fault_plan=plan,
                   retry=FAST_RETRY) as runner:
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                                   runner=runner).mine(db)
    assert res.itemsets == clean.itemsets


# -- speculative execution of stragglers ------------------------------------

def test_pooled_straggler_speculation(db, clean):
    """A hung mapper attempt is raced by a speculative backup; the backup's
    result wins, the hang never serializes the job, counts stay exact."""
    plan = FaultPlan(F.hang(delay=2.0, k=2, slot=0))
    policy = RetryPolicy(backoff=0.001, timeout=0.15)
    with SimRunner(structure="trie", executor="thread", fault_plan=plan,
                   retry=policy) as runner:
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                                   runner=runner).mine(db)
    assert res.itemsets == clean.itemsets
    assert sum(p.speculative_launches for p in res.levels) >= 1
    assert sum(p.speculative_wins for p in res.levels) >= 1
    k2 = next(p for p in res.levels if p.k == 2)
    # the k=2 job finished in the backup's time, not the hang's 2 seconds
    assert k2.seconds < 2.0


def test_sequential_straggler_speculation(db, clean):
    """The simulated (sequential) cluster models the same speculative kill:
    it waits out the timeout window instead of the full hang."""
    plan = FaultPlan(F.hang(delay=5.0, k=2, slot=1))
    policy = RetryPolicy(backoff=0.001, timeout=0.05)
    runner = SimRunner(structure="trie", fault_plan=plan, retry=policy)
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(db)
    assert res.itemsets == clean.itemsets
    assert sum(p.speculative_wins for p in res.levels) >= 1
    assert sum(p.seconds for p in res.levels) < 5.0


# -- retry exhaustion and pool lifecycle ------------------------------------

@pytest.mark.parametrize("executor", [None, "thread"])
def test_retry_exhaustion_raises_job_failed(db, executor):
    policy = RetryPolicy(max_attempts=2, backoff=0.001)
    plan = FaultPlan(F.crash(k=2, slot=0, attempt=None, times=10))
    runner = SimRunner(structure="trie", executor=executor, fault_plan=plan,
                       retry=policy)
    with pytest.raises(JobFailedError, match="slot 0"):
        FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(db)
    # the failure path must not leak the runner-owned pool
    assert runner._pool is None


def test_retry_disabled_fast_path_propagates_crash(db, clean):
    """retry=None is the pre-fault-tolerance fast path: a clean run counts
    exactly as the recovery layer's does, injected faults are not caught,
    and the pool is still closed on the way out."""
    with SimRunner(structure="trie", executor="thread", retry=None) as runner:
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                                   runner=runner).mine(db)
    assert res.itemsets == clean.itemsets
    plan = FaultPlan(F.crash(k=2, slot=0))
    runner = SimRunner(structure="trie", executor="thread", fault_plan=plan,
                       retry=None)
    with pytest.raises(MapperCrashError):
        FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(db)
    assert runner._pool is None


def test_context_manager_closes_pool(db):
    with SimRunner(structure="trie", executor="thread") as runner:
        FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(db)
        assert runner._pool is not None
    assert runner._pool is None


# -- crash-safe self-validating checkpoints ---------------------------------

def _two_snapshots(d):
    ckpt.save(str(d), 1, {"x": np.arange(4, dtype=np.int64)},
              extra={"tag": "one"})
    ckpt.save(str(d), 2, {"x": np.arange(8, dtype=np.int64)},
              extra={"tag": "two"})


def _flip_mid_byte(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))


def _truncate_half(path):
    with open(path, "r+b") as f:
        f.truncate(max(0, os.path.getsize(path) // 2))


CORRUPTIONS = {
    "tensor-flip": ("step_00000002/t00000.npy", _flip_mid_byte),
    "tensor-truncate": ("step_00000002/t00000.npy", _truncate_half),
    "manifest-truncate": ("step_00000002/manifest.json", _truncate_half),
    "manifest-flip": ("step_00000002/manifest.json", _flip_mid_byte),
    "latest-dangling": ("LATEST",
                        lambda p: open(p, "w").write("step_99999999")),
    "latest-truncate": ("LATEST", _truncate_half),
}


@pytest.mark.parametrize("mode", list(CORRUPTIONS))
def test_corruption_falls_back_or_fails_loud(tmp_path, mode):
    """Flip/truncate every file class in a snapshot: restore must either
    fall back to a pristine snapshot or fail loudly — never silently hand
    back corrupted state."""
    _two_snapshots(tmp_path)
    rel, mutate = CORRUPTIONS[mode]
    mutate(str(tmp_path / rel))
    try:
        out = ckpt.load(str(tmp_path))
    except CheckpointCorruptError:
        return  # loud failure is an accepted outcome
    assert out is not None
    tensors, step, extra = out
    expected = {1: np.arange(4, dtype=np.int64),
                2: np.arange(8, dtype=np.int64)}
    # whichever snapshot was restored, it is internally pristine
    assert extra["tag"] == {1: "one", 2: "two"}[step]
    np.testing.assert_array_equal(tensors["x"], expected[step])
    if mode.startswith(("tensor", "manifest")):
        assert step == 1  # newest was damaged: fell back
        assert (tmp_path / "step_00000002.corrupt").exists()


def test_all_snapshots_corrupt_raises(tmp_path):
    _two_snapshots(tmp_path)
    _flip_mid_byte(str(tmp_path / "step_00000001/t00000.npy"))
    _flip_mid_byte(str(tmp_path / "step_00000002/t00000.npy"))
    with pytest.raises(CheckpointCorruptError):
        ckpt.load(str(tmp_path))


def test_bitrot_injection_detected_on_restore(tmp_path):
    plan = FaultPlan(F.bitrot(step=2, tensor=0))
    ckpt.save(str(tmp_path), 1, {"x": np.arange(4)}, extra={"tag": "one"})
    ckpt.save(str(tmp_path), 2, {"x": np.arange(8)}, extra={"tag": "two"},
              fault_plan=plan)
    tensors, step, extra = ckpt.load(str(tmp_path))
    assert step == 1 and extra["tag"] == "one"
    assert (tmp_path / "step_00000002.corrupt").exists()


def test_torn_write_never_commits(tmp_path):
    ckpt.save(str(tmp_path), 1, {"x": np.arange(4)}, extra={"tag": "one"})
    plan = FaultPlan(F.torn_write(step=2, tensor=0))
    with pytest.raises(TornWriteError):
        ckpt.save(str(tmp_path), 2, {"x": np.arange(8)}, fault_plan=plan)
    assert not (tmp_path / "step_00000002").exists()
    assert (tmp_path / "step_00000002.tmp").exists()  # torn debris
    assert ckpt.latest_valid_step(str(tmp_path)) == 1
    assert not (tmp_path / "step_00000002.tmp").exists()  # swept on restore


def test_stale_tmp_gc_on_restore(tmp_path):
    _two_snapshots(tmp_path)
    (tmp_path / "step_00000099.tmp").mkdir()
    (tmp_path / "step_00000001.corrupt").mkdir()
    assert ckpt.latest_valid_step(str(tmp_path)) == 2
    assert not (tmp_path / "step_00000099.tmp").exists()
    assert not (tmp_path / "step_00000001.corrupt").exists()


def test_unpointed_snapshot_counts_as_restorable(tmp_path):
    """Crash between the snapshot rename and the pointer update: the newer,
    complete-but-unpointed snapshot is valid restorable state."""
    _two_snapshots(tmp_path)
    ckpt.save(str(tmp_path), 3, {"x": np.arange(2)}, extra={"tag": "three"})
    latest = tmp_path / "LATEST"
    latest.write_text("step_00000002")  # rewind the pointer
    assert ckpt.latest_valid_step(str(tmp_path)) == 2  # pointer wins...
    latest.unlink()
    # ...but without a pointer, the newest valid snapshot is found by scan
    assert ckpt.latest_valid_step(str(tmp_path)) == 3


# -- kill -9 mid-save: the real process-death tests -------------------------

_KILL_CHILD = """
import sys
import numpy as np
from repro.core import FrequentItemsetMiner
from repro.core.runtime import SimRunner, FaultPlan
from repro.core.runtime import faults as F

ckpt_dir, kind, step = sys.argv[1], sys.argv[2], int(sys.argv[3])
from repro.data import quest_generator
db = quest_generator(n_transactions=300, avg_transaction_len=8,
                     n_items=50, n_patterns=30, seed=3)
spec = F.kill_write(step=step) if kind == "kill_write" else \\
    F.kill_commit(step=step)
runner = SimRunner(structure="trie", fault_plan=FaultPlan(spec))
FrequentItemsetMiner(min_support=0.05, runner=runner,
                     checkpoint_dir=ckpt_dir).mine(db)
"""


@pytest.mark.parametrize("kind", ["kill_write", "kill_commit"])
def test_kill9_mid_save_leaves_restorable_state(tmp_path, db, clean, kind):
    """A subprocess is killed (os._exit(137)) mid-checkpoint — either while
    writing a tensor or after the snapshot rename but before the pointer
    update.  The parent must restore from what is on disk and finish with
    bit-identical results."""
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_CHILD, str(tmp_path), kind, "5"],
        env=_subprocess_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 137, proc.stderr[-2000:]
    if kind == "kill_write":
        # the torn .tmp never became a snapshot
        assert (tmp_path / "step_00000005.tmp").exists()
        assert not (tmp_path / "step_00000005").exists()
    else:
        # the snapshot committed but the pointer did not move to it
        assert (tmp_path / "step_00000005").exists()
        pointed = (tmp_path / "LATEST").read_text().strip()
        assert pointed != "step_00000005"
    runner = _JobCountingRunner(structure="trie")
    res = FrequentItemsetMiner(
        min_support=MIN_SUPPORT, runner=runner,
        checkpoint_dir=str(tmp_path)).mine(db)
    assert res.itemsets == clean.itemsets
    # resumed mid-run: strictly fewer jobs than a fresh mine (job1 + 5 levels)
    assert 0 < runner.jobs_run < len(clean.levels)


# -- elastic recovery from device loss --------------------------------------

_ELASTIC_CHILD = """
import os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import FrequentItemsetMiner
from repro.core.runtime import ShardedRunner, SimRunner, FaultPlan
from repro.core.runtime import faults as F
from repro.launch.mesh import make_data_cand_mesh
from repro.data import quest_generator

assert jax.device_count() == 4
db = quest_generator(n_transactions=300, avg_transaction_len=8,
                     n_items=50, n_patterns=30, seed=3)
clean = FrequentItemsetMiner(
    min_support=0.05, runner=SimRunner(structure="trie")).mine(db)
with tempfile.TemporaryDirectory() as d:
    plan = FaultPlan(F.device_loss(k=3, lost=2))
    runner = ShardedRunner(store="perfect_hash", mesh=make_data_cand_mesh(),
                           cand_axes=("cand",), fault_plan=plan)
    miner = FrequentItemsetMiner(min_support=0.05, runner=runner,
                                 checkpoint_dir=d)
    res = miner.mine(db)
    assert plan.injected, "device loss never fired"
    new_mesh = miner.active_runner.engine.mesh
    assert new_mesh.devices.size == 2, new_mesh.devices.shape
    assert res.itemsets == clean.itemsets, "elastic resume changed results"
print("ELASTIC_OK")
"""


def test_elastic_device_loss_recovery_subprocess():
    """Kill half of a forced-4-device mesh at the k=3 dispatch: the miner
    rebuilds the largest valid mesh on the 2 survivors, restores the level
    checkpoint, and finishes with itemsets AND supports bit-identical."""
    proc = subprocess.run(
        [sys.executable, "-c", _ELASTIC_CHILD], env=_subprocess_env(),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ELASTIC_OK" in proc.stdout


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_elastic_recovery_without_checkpoint(db, clean):
    """No checkpoint_dir: the elastic restart deterministically recomputes
    from scratch on the shrunk mesh — still bit-identical."""
    from repro.launch.mesh import make_data_mesh

    plan = FaultPlan(F.device_loss(k=2, lost=1))
    runner = ShardedRunner(store="perfect_hash", mesh=make_data_mesh(),
                           fault_plan=plan)
    miner = FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner)
    res = miner.mine(db)
    assert plan.injected
    assert res.itemsets == clean.itemsets
    survivors = miner.active_runner.engine.mesh.devices.size
    assert survivors == jax.device_count() - 1


def test_single_device_loss_is_fatal(db):
    """JaxRunner has no mesh to shrink: device loss propagates."""
    plan = FaultPlan(F.device_loss(k=2))
    runner = JaxRunner(store="perfect_hash", fault_plan=plan)
    with pytest.raises(DeviceLostError):
        FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(db)


def test_elastic_restart_budget_exhaustion(db):
    """More losses than elastic_restarts allows: the run dies loudly."""
    plan = FaultPlan(F.device_loss(k=2, times=10))
    runner = JaxRunner(store="perfect_hash", fault_plan=plan)
    miner = FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner,
                                 elastic_restarts=0)
    with pytest.raises(DeviceLostError):
        miner.mine(db)


# -- checkpoint config stamping under elasticity ----------------------------

def test_config_signature_excludes_elastic_geometry():
    """The checkpoint stamp must survive mesh/mapper-count changes (elastic
    resume) while still distinguishing backend kind and store/structure."""
    assert SimRunner(structure="trie").config_signature() == \
        SimRunner(structure="trie", n_mappers=8,
                  executor="thread").config_signature()
    assert SimRunner(structure="trie").config_signature() != \
        SimRunner(structure="hash_tree").config_signature()
    a = JaxRunner(store="perfect_hash")
    b = JaxRunner(store="packed_bitmap")
    assert a.config_signature() != b.config_signature()
    assert a.config_signature() != SimRunner(
        structure="trie").config_signature()


def test_miner_resumes_across_mapper_count_change(tmp_path, db, clean):
    """A Hadoop job restart on a reprovisioned cluster (different mapper
    slots) resumes the same logical run from its checkpoint."""
    FrequentItemsetMiner(min_support=MIN_SUPPORT,
                         runner=SimRunner(structure="trie", n_mappers=3),
                         checkpoint_dir=str(tmp_path)).mine(db)
    # the completed run's final checkpoint carries the whole result: a
    # restart with a different slot count must accept the stamp and re-run
    # nothing (the generation from the last level is empty)
    runner = _JobCountingRunner(structure="trie", n_mappers=6)
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner,
                               checkpoint_dir=str(tmp_path)).mine(db)
    assert res.itemsets == clean.itemsets
    assert runner.jobs_run == 0  # it truly resumed


def test_miner_rejects_cross_structure_resume(tmp_path, db, clean):
    FrequentItemsetMiner(min_support=MIN_SUPPORT,
                         runner=SimRunner(structure="trie"),
                         checkpoint_dir=str(tmp_path)).mine(db)
    runner = _JobCountingRunner(structure="hash_tree")
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner,
                               checkpoint_dir=str(tmp_path)).mine(db)
    assert res.itemsets == clean.itemsets
    assert runner.jobs_run == len(clean.levels)  # full re-mine, no resume


# -- multi-host: env-selected roles, process death, elastic relaunch ---------
from repro.data import get_dataset  # noqa: E402
from repro.distributed import ctx as _mctx  # noqa: E402
from repro.launch import multihost as mh  # noqa: E402


def test_multihost_env_parsing():
    assert _mctx.multihost_env(env={}) is None
    spec = _mctx.multihost_env(env={
        "REPRO_COORDINATOR": "127.0.0.1:9999",
        "REPRO_NUM_PROCESSES": "2", "REPRO_PROCESS_ID": "1"})
    assert spec == _mctx.MultihostSpec("127.0.0.1:9999", 2, 1)
    # A partial trio is a launch bug, never a silent single-process run.
    with pytest.raises(ValueError, match="REPRO_"):
        _mctx.multihost_env(env={"REPRO_COORDINATOR": "127.0.0.1:9999"})
    with pytest.raises(ValueError):
        _mctx.multihost_env(env={
            "REPRO_COORDINATOR": "c:1", "REPRO_NUM_PROCESSES": "two",
            "REPRO_PROCESS_ID": "0"})
    with pytest.raises(ValueError):  # pid out of [0, num)
        _mctx.multihost_env(env={
            "REPRO_COORDINATOR": "c:1", "REPRO_NUM_PROCESSES": "2",
            "REPRO_PROCESS_ID": "2"})


def test_process_exit_fault_addressing():
    plan = FaultPlan(F.process_exit(k=3, process=1))
    assert plan.process_exit(k=2, process=1) is None
    assert plan.process_exit(k=3, process=0) is None
    spec = plan.process_exit(k=3, process=1)
    assert spec is not None and spec.kind == "process_exit"
    # One-shot: the relaunched cluster must not die again.
    assert plan.process_exit(k=3, process=1) is None
    assert [kind for kind, _ in plan.injected] == ["process_exit"]


def test_worker_env_trio_and_device_flags():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 --foo"}
    env = mh.worker_env("127.0.0.1:5555", 3, 2, local_devices=2, base=base)
    assert env["REPRO_COORDINATOR"] == "127.0.0.1:5555"
    assert env["REPRO_NUM_PROCESSES"] == "3"
    assert env["REPRO_PROCESS_ID"] == "2"
    # The inherited force flag is replaced, not duplicated.
    assert env["XLA_FLAGS"].count("device_count") == 1
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
    assert "--foo" in env["XLA_FLAGS"]
    # Workers are a CPU rehearsal: none may claim an accelerator.
    assert env["JAX_PLATFORMS"] == "cpu"
    assert _mctx.multihost_env(env=env) == _mctx.MultihostSpec(
        "127.0.0.1:5555", 3, 2)


class _FakeProc:
    """Scripted worker: returns its exit code after `alive_polls` polls."""

    def __init__(self, rc, alive_polls=0):
        self.rc = rc
        self.alive_polls = alive_polls
        self.killed = False

    def poll(self):
        if self.alive_polls > 0:
            self.alive_polls -= 1
            return None
        return self.rc

    def kill(self):
        self.killed = True
        self.alive_polls = 0

    def wait(self, timeout=None):
        return self.rc


def test_launch_cluster_success_and_failure():
    spawned = []

    def fake_popen(script):
        def popen(argv, env):
            p = _FakeProc(*script[len(spawned)])
            spawned.append((argv, env, p))
            return p
        return popen

    # All clean: returns the coordinator, kills nobody.
    spawned.clear()
    coord = mh.launch_cluster(["prog"], 2, coordinator="127.0.0.1:7000",
                              popen=fake_popen([(0, 1), (0, 1)]))
    assert coord == "127.0.0.1:7000"
    assert [e["REPRO_PROCESS_ID"] for _, e, _ in spawned] == ["0", "1"]
    assert not any(p.killed for _, _, p in spawned)

    # Worker 1 dies rc=137 while worker 0 hangs: 0 is killed, failure names 1.
    spawned.clear()
    with pytest.raises(mh.ClusterFailure) as ei:
        mh.launch_cluster(["prog"], 2,
                          popen=fake_popen([(0, 10**9), (137, 1)]))
    assert (ei.value.process_id, ei.value.returncode) == (1, 137)
    assert spawned[0][2].killed and not spawned[1][2].killed

    # Nobody finishes: the timeout kills the cluster loudly.
    spawned.clear()
    with pytest.raises(TimeoutError):
        mh.launch_cluster(["prog"], 2, timeout=0.0, poll_interval=0.0,
                          popen=fake_popen([(0, 10**9), (0, 10**9)]))
    assert all(p.killed for _, _, p in spawned)


@pytest.mark.slow
@pytest.mark.mesh
def test_multihost_killed_worker_recovery(tmp_path):
    """The tentpole's real-process-failure story, end to end: a 2-process
    gloo cluster loses worker 1 to os._exit(137) at the k=3 dispatch, the
    supervisor kills the hung survivor and relaunches 1 process from the
    shared checkpoint dir, and the resumed mine is bit-identical —
    itemsets AND supports — to a clean single-process run."""
    out = str(tmp_path / "result.json")
    args = mh._parse([
        "--processes", "2", "--kill-k", "3", "--kill-process", "1",
        "--min-support", "0.015", "--scale", "0.002",
        "--checkpoint-dir", str(tmp_path / "ckpt"), "--out", out,
        "--timeout", "520"])
    summary = mh.supervise(args)
    assert summary["relaunches"] == 1
    # Which nonzero exit the supervisor observes first is a race: the
    # killed worker's os._exit(137), or the survivor erroring out of its
    # gloo collective once the peer vanishes. Either way exactly one
    # launch failed and triggered the shrunk relaunch.
    assert len(summary["failures"]) == 1
    failed_process, returncode = summary["failures"][0]
    assert failed_process in (0, 1) and returncode != 0
    assert summary["final_processes"] == 1
    result = summary["result"]
    assert result["restored_step"] is not None and result["restored_step"] >= 2
    clean = FrequentItemsetMiner(min_support=0.015, max_k=6).mine(
        get_dataset("T10I4D100K", scale=0.002, seed=0))
    expected = sorted([list(s), int(c)] for s, c in clean.itemsets.items())
    assert result["itemsets"] == expected
    assert result["n_transactions"] == clean.n_transactions
    assert result["min_count"] == clean.min_count
    # Restored levels ride the checkpoint into the resumed run's profile
    # list, so the job ledger matches a clean mine exactly — nothing was
    # double-counted, nothing skipped.
    assert result["counting_jobs"] == sum(1 for p in clean.levels if p.k >= 2)


def test_supervise_relaunches_smaller_without_fault(tmp_path, monkeypatch):
    """Supervisor logic in isolation (no real cluster): first launch dies,
    the relaunch runs one process smaller and drops the kill args, the
    summary carries the failure ledger and the worker's result JSON."""
    out = str(tmp_path / "result.json")
    args = mh._parse(["--processes", "2", "--kill-k", "3",
                      "--checkpoint-dir", str(tmp_path / "ck"),
                      "--out", out, "--elastic", "1"])
    calls = []

    def fake_launch(argv, n, local_devices=1, timeout=None):
        calls.append((list(argv), n))
        if len(calls) == 1:
            assert "--kill-k" in argv
            raise mh.ClusterFailure(1, 137)
        assert "--kill-k" not in argv  # relaunches run clean
        with open(out, "w") as f:
            f.write('{"itemsets": [], "restored_step": 3}')

    monkeypatch.setattr(mh, "launch_cluster", fake_launch)
    summary = mh.supervise(args)
    assert [n for _, n in calls] == [2, 1]
    assert summary["relaunches"] == 1
    assert summary["failures"] == [(1, 137)]
    assert summary["final_processes"] == 1
    assert summary["result"]["restored_step"] == 3
    # Both launches target the same module with the same checkpoint dir.
    for argv, _ in calls:
        assert argv[1:3] == ["-m", "repro.launch.multihost"]
        assert str(tmp_path / "ck") in argv

    # The elastic budget is finite: a second failure propagates.
    args2 = mh._parse(["--processes", "2", "--checkpoint-dir",
                       str(tmp_path / "ck2"), "--out", out, "--elastic", "0"])

    def always_fail(argv, n, local_devices=1, timeout=None):
        raise mh.ClusterFailure(0, 1)

    monkeypatch.setattr(mh, "launch_cluster", always_fail)
    with pytest.raises(mh.ClusterFailure):
        mh.supervise(args2)
