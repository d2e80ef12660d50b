"""The trace reduction on a small trace recorded on a TPU v5e.

``data/tiny_step.xplane.pb`` was written by
``python bench/tools/record_trace.py`` on one chip: a jitted
``tiny_step`` (one fused multiply-reduce) run three times, each inside a
host span ``bench.step``.
"""

from pathlib import Path

import pytest

from bench import trace

TINY = Path(__file__).parent / "data" / "tiny_step.xplane.pb"


@pytest.fixture(scope="module")
def tiny():
    return trace.reduce(str(TINY))


def test_one_chip_and_its_program(tiny):
    assert tiny.n_devices == 1
    assert [trace.program_base(p) for p in tiny.program_s] == ["jit_tiny_step"]
    # Three runs of about 2.2 us of device time each.
    assert 5e-6 < tiny.busy_s < 8e-6
    assert tiny.busy_s == pytest.approx(sum(tiny.program_s.values()),
                                        rel=0.01)
    assert tiny.device_ops[0][0] == "jit_tiny_step/multiply_reduce_fusion"


def test_window_and_idle_gaps(tiny):
    # No bench.window span: the window runs from the first op to the last.
    assert tiny.window_s > tiny.busy_s
    assert 0.99 < tiny.idle_share < 1.0
    labels = {label for label, _ in tiny.idle_gaps}
    assert labels <= {"step", "other"} and "step" in labels
    gaps = [s for _, s in tiny.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) == pytest.approx(tiny.window_s - tiny.busy_s, rel=1e-6)


def test_matching_seconds(tiny):
    both = tiny.program_s[next(iter(tiny.program_s))]
    assert trace.matching_seconds(tiny, [{"program": "jit_tiny_step"}]) == both
    assert trace.matching_seconds(tiny, [{"program": "jit_other"}]) == 0.0
    assert trace.matching_seconds(
        tiny, [{"program": "jit_tiny_step", "with_op": "multiply"}]) == both
    assert trace.matching_seconds(
        tiny, [{"program": "jit_tiny_step", "with_op": "while"}]) == 0.0


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_no_device_plane_is_an_error(tmp_path):
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        jnp.ones(4).block_until_ready()
    path = next(tmp_path.rglob("*.xplane.pb"))
    if jax.default_backend() == "cpu":
        with pytest.raises(ValueError, match="no device plane"):
            trace.reduce(str(path))
