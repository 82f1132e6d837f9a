"""The trace reduction, on hand-made intervals and on a small trace recorded
on a TPU v5e chip in PR 25 (``bench_cells/reduce/record_fixture.py``: three
runs of one small program under the harness's spans, a 2 ms host sleep
after each)."""

import os

import pytest

from bench_cells.reduce import xplane
from bench_cells.reduce.xplane import Device, Event, Trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture.xplane.pb")


def test_merge_total_subtract_gaps():
    iv = [(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]
    assert xplane.merge(iv) == [(1, 4), (5, 8)]
    assert xplane.total(xplane.merge(iv)) == 6
    assert xplane.clip([(1, 4), (5, 8)], 3, 6) == [(3, 4), (5, 6)]
    assert xplane.subtract([(0, 10)], [(1, 4), (5, 8)]) == [
        (0, 1), (4, 5), (8, 10)]
    assert xplane.subtract([(2, 6)], [(0, 3), (5, 9)]) == [(3, 5)]
    assert xplane.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_gap_goes_to_the_innermost_span():
    spans = [Event("bench.outer", 0.0, 10.0), Event("bench.inner", 2.0, 4.0)]
    got = xplane.attribute_gaps([(1.0, 5.0), (11.0, 12.0)], spans)
    assert got == pytest.approx({"bench.outer": 2.0, "bench.inner": 2.0,
                                 "(no span)": 1.0})


def test_exposed_collective_is_what_no_other_operation_covers():
    dev = Device(0, [Event("all-reduce.3", 0.0, 4.0),
                     Event("fusion.1", 1.0, 2.0),
                     Event("collective-permute-done.2", 6.0, 7.0),
                     Event("fusion.2", 8.0, 9.0)], [])
    assert xplane.exposed_collective_seconds(dev) == pytest.approx(4.0)


def test_ops_within_program_runs():
    dev = Device(0, [Event("a", 0.5, 1.0), Event("b", 2.5, 3.0),
                     Event("c", 4.0, 4.5)],
                 [Event("jit_step(1)", 0.0, 2.0), Event("jit_other(2)", 2.0,
                                                        3.5),
                  Event("jit_step(1)", 3.9, 5.0)])
    runs = xplane.module_runs(dev, "^jit_step")
    assert [e.name for e in xplane.ops_within(dev, runs)] == ["a", "c"]
    trace = Trace([dev], [Event("bench.x", 0.0, 6.0)])
    assert trace.bounds == (0.0, 6.0)
    assert xplane.busy_seconds(trace) == pytest.approx(1.5)
    assert xplane.busy_seconds(trace, 0.75, 4.25) == pytest.approx(1.0)


def test_op_name_is_the_instruction():
    line = ("%fusion.75.remat_compressed = bf16[36,1025]{1,0:T(8,128)} "
            "copy(bf16[36,1025]{0,1} %fusion.75)")
    assert xplane.op_name(line) == "fusion.75.remat_compressed"
    assert xplane.op_name("plain") == "plain"
    assert xplane.op_kind(line) == "copy"
    loop = ("%while.72 = (s32[]{:T(128)}, f32[1,1310720]{1,0:T(1,128)}) "
            "while((s32[]{:T(128)}, f32[1,1310720]{1,0:T(1,128)}) %tuple.3), "
            "condition=%cond, body=%body")
    assert xplane.op_kind(loop) == "while" in xplane.CONTAINERS
    assert xplane.op_kind("plain") == ""


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(FIXTURE)


def test_recorded_trace_structure(recorded):
    assert os.path.getsize(FIXTURE) < 1_000_000
    assert len(recorded.devices) == 1
    dev = recorded.devices[0]
    assert (len(dev.ops), len(dev.modules), len(recorded.spans)) == (9, 3, 12)
    assert all(m.name.startswith("jit_fixture_step") for m in dev.modules)
    assert len(xplane.ops_within(dev, dev.modules)) == 9


def test_recorded_busy_idle_and_time_by_name(recorded):
    lo, hi = recorded.bounds
    assert (lo, hi) == pytest.approx((0.043792701, 0.054635835), abs=1e-9)
    busy = xplane.busy_seconds(recorded)
    # its operations run one after another, so the union is their sum
    assert busy == pytest.approx(
        sum(e.seconds for e in recorded.devices[0].ops), abs=1e-12)
    assert busy == pytest.approx(7.1618e-05, abs=1e-9)
    assert 1 - busy / (hi - lo) == pytest.approx(0.993395, abs=1e-6)
    by_name = xplane.seconds_by_name(recorded.devices[0].ops)
    assert by_name == pytest.approx({"convolution_reduce_fusion": 7.157e-05,
                                     "copy-start": 4e-08,
                                     "copy-done": 8e-09}, abs=1e-10)
    assert xplane.top_ops(recorded, 1)[0][0] == "convolution_reduce_fusion"


def test_recorded_idle_gaps_by_span(recorded):
    got = dict(xplane.idle_by_span(recorded))
    assert got == pytest.approx({
        "bench.fixture.sleep": 0.007215196,
        "bench.fixture.block": 0.00147632,
        "bench.fixture.dispatch": 0.00102077,
        "(no span)": 0.000973391,
        "bench.fixture.step": 8.5839e-05}, abs=1e-8)
    lo, hi = recorded.bounds
    assert sum(got.values()) == pytest.approx(
        (hi - lo) - xplane.busy_seconds(recorded), abs=1e-9)


def test_a_trace_without_device_operations_is_an_error(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    with pytest.raises(SystemExit, match="no device operation"):
        xplane.load(str(path))
