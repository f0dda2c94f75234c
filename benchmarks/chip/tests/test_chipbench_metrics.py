"""The readers that turn a trace summary into per-layer metrics, and
the kernel's operation and byte counts."""
import pytest

from benchmarks.chip import harness, tracefile

METRICS = harness.HERE / "metrics"


def reader(name):
    return harness.load_module(METRICS / f"{name}.py")


def synthetic():
    """Two devices over a 100 ns window, three jobs: device 0 runs a
    kernel, a fusion and an all-reduce partly hidden behind the fusion;
    device 1 is busy for 40 ns."""
    return {
        "window": [0, 100], "jobs": 3,
        "devices": {
            "0": [["matmul_rank1.3", 0, 20],
                  ["fusion.7", 30, 20],
                  ["all-reduce.1", 40, 30],
                  ["fusion.7", 90, 20]],          # runs past the end
            "1": [["fusion.2", 10, 40]]},
        "host": [["PjitFunction(factorize)", 18, 15],
                 ["TransferToDevice", 70, 25]],
    }


def test_intervals():
    s = synthetic()
    ops = s["devices"]["0"]
    assert tracefile.busy_ns(ops, s["window"]) == 20 + 40 + 10
    assert tracefile.gaps(ops, s["window"]) == [(20, 30), (70, 90)]
    assert tracefile.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_device_idle_share():
    # device 0 idle 30 of 100, device 1 idle 60 of 100
    assert reader("device_idle_share").read(synthetic(), {}) == \
        pytest.approx(45.0)


def test_device_idle_share_reads_nothing_without_ops():
    s = synthetic()
    s["devices"] = {"0": []}
    assert reader("device_idle_share").read(s, {}) is None


def test_breakdown_names_gaps_by_host_events():
    b = tracefile.breakdown(synthetic())
    assert b["device_ops"][0][0] == "fusion.2"
    assert [g[0] for g in b["idle_gaps"]] == ["TransferToDevice",
                                              "PjitFunction(factorize)"]
    assert b["idle_gaps"][0][1] == pytest.approx(20e-9)


LFW = (62500, 13233, 200)


def test_kernel_counts_at_lfw_shape():
    cost = reader("matmul_rank1_roofline").cost
    m, n, K = LFW
    # X @ B: X, B (n, K), u (m), w (K), out (m, K)
    assert cost(m, n, K, False) == (2 * m * n * K + 2 * m * K,
                                    4 * (m * n + n * K + m + K + m * K))
    # X^T @ B: X, B (m, K), u (n), w (K), out (n, K)
    assert cost(m, n, K, True) == (2 * m * n * K + 2 * n * K,
                                   4 * (m * n + m * K + n + K + n * K))
    assert cost(m, n, K, False) == (330_850_000_000, 3_369_087_200)
    assert cost(m, n, K, True) == (330_830_293_200, 3_368_890_132)


def test_kernel_counts_follow_logical_not_padded_shapes():
    cost = reader("matmul_rank1_roofline").cost
    m, n, K = LFW
    # the kernel pads X to (62720, 13312) tiles; the counts do not move
    assert cost(m, n, K, False) != cost(62720, 13312, K, False)
    # an aligned shape is counted by the same formula
    assert cost(32768, 32768, 128, True) == (
        2 * 32768 * 32768 * 128 + 2 * 32768 * 128,
        4 * (32768 * 32768 + 2 * 32768 * 128 + 32768 + 128))


def test_roofline_share_on_a_synthetic_trace():
    mod = reader("matmul_rank1_roofline")
    cell = harness.load_cell("lfw_faces.q2")
    peaks = harness.peaks_for("TPU v5 lite")
    least = min(mod.least_seconds(*LFW, t, peaks) for t in (False, True))
    # HBM bound: 3.3 GB of X at 819 GB/s, about 4.1 ms
    assert 4.0e-3 < least < 4.2e-3
    t_least = least_seconds_of(mod, peaks, True)
    f_least = least_seconds_of(mod, peaks, False)
    s = {"window": [0, 10 ** 9], "jobs": 1,
         "devices": {"0": [
             ["%matmul_rank1.1 = f32[13312,256]{1,0} custom-call(...)",
              0, int(2e9 * t_least)],
             ["%slice.0 = f32[13233,200]{1,0} slice(f32[13312,256] "
              "%matmul_rank1.1)", 0, 5],
             ["%matmul_rank1.1 = f32[62720,256]{1,0} custom-call(...)",
              0, int(1e9 * f_least)]]}, "host": []}
    share = mod.read(s, {"cell": cell, "peaks": peaks})
    # one event at half its roofline, one at all of it
    assert share == pytest.approx(
        100.0 * (t_least + f_least) / (2 * t_least + f_least), rel=1e-6)
    assert mod.orientation(s["devices"]["0"][1][0], *LFW[:2]) is None


def least_seconds_of(mod, peaks, transpose):
    return mod.least_seconds(*LFW, transpose, peaks)


RECORDED = harness.HERE / "tests" / "fixtures" / \
    "lfw_faces.q2.summary.json.gz"


def test_readers_on_a_recorded_chip_trace():
    """Two jobs of ``lfw_faces.q2`` traced on a TPU v5e, op
    names cut to the instruction and its result."""
    s = tracefile.load(RECORDED)
    info = {"cell": harness.load_cell("lfw_faces.q2"),
            "peaks": harness.peaks_for("TPU v5 lite")}
    assert reader("device_idle_share").read(s, info) == \
        pytest.approx(46.3638996677576, rel=1e-9)
    assert reader("matmul_rank1_roofline").read(s, info) == \
        pytest.approx(53.711145892343005, rel=1e-9)
    b = tracefile.breakdown(s)
    assert b["device_ops"][0] == ["%copy.3 = f32[62500,13233]{1,0:T(8,128)}",
                                  pytest.approx(0.090234854)]
    assert b["idle_gaps"][1][0] == "PjitFunction(scan)"
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
