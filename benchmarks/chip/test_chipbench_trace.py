"""The trace reduction, on a small trace recorded on one TPU v5e.

``testdata/explore.xplane.pb`` holds, in this order: a 32 MiB host-to-device
copy inside a ``bench.h2d`` span, three RAAR steps (64 frames of 32² on a
128² object, Pallas kernels) inside ``bench.raar``, a 50 ms host sleep
inside ``bench.idle``, and one ART call of 8 slices inside ``bench.art``.
"""
import os

import pytest

from chipbench import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                     "explore.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return xplane.reduce_trace(TRACE)


def test_op_names_are_stable():
    assert xplane.op_name("%fusion.18 = f32[64,32,32]{0,1,2} fusion(%a)") \
        == "fusion"
    assert xplane.op_name("%modulus_project.1 = (f32[2]) custom-call()") \
        == "modulus_project"
    assert xplane.op_name("%copy-start = (f32[1]) copy-start(%x)") \
        == "copy-start"


def test_interval_union_and_cover():
    m = xplane.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m == [(0, 3), (5, 9)]
    assert xplane.covered(m, 2, 6) == 2
    assert xplane.covered(m, -5, 100) == 7
    assert xplane.covered([], 0, 1) == 0


def test_busy_idle_and_ops(red):
    assert red.chips == 1
    assert 0 < red.busy_s < red.window_s
    names = dict(red.top_ops(20))
    for kernel in ("modulus_project", "overlap_products", "raar_combine",
                   "vmap_jit_art_sweep__"):
        assert names.get(kernel, 0) > 0
    assert red.op_seconds("art_sweep") == pytest.approx(
        names["vmap_jit_art_sweep__"])
    # the three RAAR steps are busy inside their span, the sleep is not
    raar = red.spans_named("bench.raar")[0]
    idle = red.spans_named("bench.idle")[0]
    assert red.busy_between(raar.start_ns, raar.end_ns) > 0.01
    assert red.busy_between(idle.start_ns, idle.end_ns) < 1e-3


def test_host_to_device_copies(red):
    sizes = sorted(size for _, _, size in red.h2d)
    assert sizes[-1] == 32 << 20
    big = [(s, e) for s, e, size in red.h2d if size == 32 << 20][0]
    assert 0 < (big[1] - big[0]) * 1e-9 < 0.1


def test_idle_gaps_are_named_by_host_span(red):
    gaps = red.idle_gaps(10)
    assert gaps[0][0] == "bench.idle"
    assert gaps[0][1] > 0.04
    assert sum(v for _, v in gaps) == pytest.approx(
        red.window_s - red.busy_s)
