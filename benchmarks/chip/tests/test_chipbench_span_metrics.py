"""The readers of the program's own ``repro.*`` spans: lowerings, idle
time inside the Givens update, idle time outside every span, and the
passes over X."""
import pytest

from benchmarks.chip import harness, tracefile

METRICS = harness.HERE / "metrics"
FIXTURES = harness.HERE / "tests" / "fixtures"
#: A chip trace of a program from before the spans.
RECORDED = FIXTURES / "lfw_faces.q2.summary.json.gz"
#: A chip trace of the program with its spans.
SPANNED = FIXTURES / "lfw_faces.q2.spans.summary.json.gz"
READERS = ["lowerings_per_job", "shift_update_idle_ms",
           "unspanned_idle_share", "x_passes_per_job"]


def reader(name):
    return harness.load_module(METRICS / f"{name}.py")


def unspanned():
    """One device over a 100 ns window, three jobs, and only JAX's own
    host events: a program without ``repro.obs``."""
    return {
        "window": [0, 100], "jobs": 3,
        "devices": {"0": [["matmul_rank1.3", 0, 20], ["fusion.7", 30, 20]]},
        "host": [["PjitFunction(factorize)", 18, 15],
                 ["TransferToDevice", 70, 25]],
    }


def spanned():
    """One device over a 100 ns window, two jobs, with the program's
    spans: the device idles over (20, 40) and (50, 80); the jobs'
    ``repro.factorize`` spans leave (45, 55) and (95, 100) uncovered."""
    return {
        "window": [0, 100], "jobs": 2,
        "devices": {"0": [["a", 0, 20], ["b", 40, 10], ["c", 80, 20]]},
        "host": [["repro.factorize", 0, 45],
                 ["repro.factorize", 55, 40],
                 # (20, 30) of the first gap lies inside, (30, 40) not
                 ["repro.shift_update", 10, 20],
                 ["repro.shift_update", 60, 10],
                 ["repro.lowered", 12, 0],
                 ["repro.lowered", 14, 1],
                 ["repro.lowered", 61, 0],
                 # a composed contact counts the passes inside it: one
                 # leaf in each of the first job's two contacts, two in
                 # the second job's
                 ["repro.contact", 2, 6], ["repro.contact", 3, 2],
                 ["repro.contact", 32, 5],
                 ["repro.contact", 56, 30], ["repro.contact", 60, 5],
                 ["repro.contact", 70, 5],
                 ["PjitFunction(scan)", 46, 8]],
    }


def test_lowerings_per_job_counts_markers():
    assert reader("lowerings_per_job").read(spanned(), {}) == 1.5


def test_lowerings_per_job_reads_zero_without_markers():
    s = spanned()
    s["host"] = [h for h in s["host"] if h[0] != "repro.lowered"]
    assert reader("lowerings_per_job").read(s, {}) == 0.0


def test_shift_update_idle_counts_only_gaps_inside_the_span():
    # (20, 30) and (60, 70): 20 ns over two jobs
    assert reader("shift_update_idle_ms").read(spanned(), {}) == \
        pytest.approx(10e-6)


def test_unspanned_idle_share_is_idle_outside_every_span():
    # idle (50, 80) meets uncovered (45, 55) in 5 ns of 100
    assert reader("unspanned_idle_share").read(spanned(), {}) == \
        pytest.approx(5.0)


def test_x_passes_per_job_counts_nested_contacts_once():
    assert reader("x_passes_per_job").read(spanned(), {}) == 2.0


@pytest.mark.parametrize("contacts, passes", [
    # a dense Gram contact: X^T B, then X (X^T B), inside its span
    ([["repro.contact", 10, 50], ["repro.contact", 12, 20],
      ["repro.contact", 35, 20]], 2.0),
    # the blocked Gram contact reads each slab once for both products
    ([["repro.contact", 10, 50]], 1.0),
    # two passes side by side, touching end to start
    ([["repro.contact", 10, 20], ["repro.contact", 30, 20]], 2.0),
])
def test_x_passes_per_job_counts_the_passes_of_a_gram_contact(contacts,
                                                             passes):
    s = {"window": [0, 100], "jobs": 1,
         "devices": {"0": [["a", 0, 100]]},
         "host": [["repro.factorize", 0, 90]] + contacts}
    assert reader("x_passes_per_job").read(s, {}) == passes


@pytest.mark.parametrize("name", READERS)
def test_span_readers_read_nothing_from_a_program_without_spans(name):
    assert reader(name).read(unspanned(), {}) is None


@pytest.mark.parametrize("name", READERS)
def test_span_readers_read_nothing_from_a_recorded_trace_without_spans(name):
    """The chip trace of a program from before the spans: its host
    events are JAX's own, and no reader of the spans finds anything."""
    s = tracefile.load(RECORDED)
    assert s["host"] and s["jobs"]
    assert reader(name).read(s, {}) is None


@pytest.mark.parametrize("name, value", [
    ("lowerings_per_job", 2.0),
    ("shift_update_idle_ms", 130.72845233333334),
    ("unspanned_idle_share", 2.143646050168328),
    ("x_passes_per_job", 9.0),
])
def test_span_readers_on_a_recorded_chip_trace(name, value):
    """Three jobs of ``lfw_faces.q2`` traced on a TPU v5e, with the
    program's ``repro.*`` spans."""
    s = tracefile.load(SPANNED)
    assert reader(name).read(s, {}) == pytest.approx(value, rel=1e-9)
