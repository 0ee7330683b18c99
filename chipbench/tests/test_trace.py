"""The trace reduction against counts made by hand on a small trace kept
in the repository (``data/two_chips.pbtxt``: two TPU planes and the
harness's host spans, in the layout of a TPU run's ``.xplane.pb``)."""
import base64
import os

import pytest
from jax.profiler import ProfileData

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNELS = {"_sv_predict_call.7": "_sv_predict_kernel",
           "vmap_jit__quadform_call__.6": "_quadform_kernel"}


@pytest.fixture(scope="module")
def summary():
    with open(os.path.join(DATA, "two_chips.pbtxt")) as f:
        return trace.reduce(ProfileData.from_text_proto(f.read()), KERNELS)


def test_union():
    busy, merged = trace.union_ns([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert merged == [(0, 3), (5, 9), (10, 10)]
    assert busy == 3 + 4


def test_kernel_names_from_hlo_line():
    body = base64.b64encode(b"\x00loc(\"kernels\")\x00_kernel_row\x00"
                            b"_sv_predict_kernel\x00_sv_predict_call\x00").decode()
    line = ('  %_sv_predict_call.7 = f32[32,1,128]{2,1,0} custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", backend_config={"custom_call_config":'
            '{"body":"' + body + '","serialization_format":"1"}}')
    other = '  %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop'
    assert trace.kernel_names(line + "\n" + other) == {
        "_sv_predict_call.7": "_sv_predict_kernel"}


def test_window_and_busy(summary):
    # the chipbench.window span runs from 1000 to 11000 ns
    assert summary.window_ns == 10000
    d0, d1 = summary.devices
    # chip 0: [500,1500) clipped to [1000,1500), [2000,3000) and [2500,4000)
    # merged, [7000,8000), [10500,11500) clipped to [10500,11000); the
    # "XLA Modules" line is not an operation
    assert d0.busy_ns == 500 + 2000 + 1000 + 500
    assert d0.gaps == [(1500, 2000), (4000, 7000), (8000, 10500)]
    assert d1.busy_ns == 500 + 6000


def test_kernels_and_collectives(summary):
    d0, d1 = summary.devices
    assert d0.kernels == {"_sv_predict_kernel": [1000, 1],
                          "_quadform_kernel": [1500, 1]}
    assert d0.collective_ns == 1000 and d1.collective_ns == 500
    assert d1.kernels == {}


def test_gap_names(summary):
    # each gap takes the shortest host span covering its middle
    assert summary.gap_names == [("chipbench.experiment", 3000),
                                 ("chipbench.experiment", 2500),
                                 ("PjitFunction(simulate)", 500)]


def test_breakdown_and_reading(summary):
    b = summary.breakdown()
    assert b["device_ops"][0] == ["fusion.2", 3000 / 1e9]
    assert dict(b["device_ops"])["fusion.1"] == 500 / 1e9
    assert b["idle_gaps"][0] == ["chipbench.experiment", 3000 / 1e9]
    r = trace.Reading(cfg={"learners": 8, "chips": 2}, traffic={}, peak=None,
                      summary=summary, rounds=20, syncs=3)
    assert r.window_s == 1e-5
    assert r.busy_s == pytest.approx((4000 + 6500) / 2 / 1e9)
    assert r.kernel("_quadform_kernel") == (1500 / 1e9, 1)
    assert r.kernel("_rff_step_kernel") is None
    assert r.learners_per_chip == 4


def test_readers_on_the_trace(summary):
    from chipbench import counts
    from chipbench.harness import load_reader

    cfg = {"kind": "sv", "learners": 8, "chips": 2, "budget": 4, "dim": 2}
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    r = trace.Reading(cfg=cfg, traffic={"protocol": {"kind": "dynamic"}}, peak=peak,
                      summary=summary, rounds=20, syncs=5)
    read = lambda name: load_reader(name)(r)
    assert read("device_idle_share") == pytest.approx(100 * (1 - 5250 / 10000))
    assert read("round_busy_us") == pytest.approx(6500 / 1e3 / 20)
    assert read("collective_share") == pytest.approx(100 * 500 / 6500)
    ops, nbytes = counts.sv_predict(4, 4, 2)
    least = max(ops / 1e12, nbytes / 1e11)
    assert read("sv_predict_roofline") == pytest.approx(100 * least / 1e-6)
    q = sum(max(o / 1e12, b / 1e11) for o, b in
            (counts.quadform(4, 4, 4, 2, 0), counts.quadform(4, 4, 4, 2, 1),
             counts.quadform(1, 4, 4, 2, 2)))
    assert read("quadform_roofline") == pytest.approx(100 * (1 / 3) * q / 1.5e-6)
    assert read("rff_step_roofline") is None
    per_round = counts.sv_round_ops(cfg, True, 5 / 20)
    assert read("step_mfu") == pytest.approx(100 * per_round * 20 / 1e-5 / (2 * 1e12))


def test_unmapped_kernels_stop_a_traced_run(tmp_path):
    """A program whose kernel calls the HLO reading cannot name stops the
    traced run, rather than leave the kernel rooflines silent."""
    from chipbench import harness

    body = base64.b64encode(b"\x00no function name\x00").decode()
    line = ('  %custom-call.3 = f32[8]{0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", backend_config={"custom_call_config":'
            '{"body":"' + body + '"}}')
    assert trace.custom_calls(line) == 1 and trace.kernel_names(line) == {}
    with pytest.raises(RuntimeError, match="name no kernel"):
        harness.read_trace(str(tmp_path), line, None, [], None, None, print)
