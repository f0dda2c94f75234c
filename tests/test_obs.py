"""The program's spans and compile counts (``repro.obs``), read back
from a profiler trace on the CPU."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.profiler import ProfileData

from repro import api, obs
from repro.core import contact
from repro.core.linop import BlockedOp, as_linop
from repro.data.pipeline import ColumnBlockLoader

PHASES = ["repro.center", "repro.sample", "repro.qr", "repro.shift_update",
          "repro.norm", "repro.power_step", "repro.project",
          "repro.small_svd", "repro.report"]


def _host_spans(trace_dir):
    """(name, start, end) of every ``repro.*`` host event in the trace."""
    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda e: e[1])


def _leaves(spans):
    """The ``repro.contact`` spans that hold no other contact."""
    contacts = [s for s in spans if s[0] == "repro.contact"]
    return [c for c in contacts
            if not any(o is not c and c[1] <= o[1] and o[2] <= c[2]
                       for o in contacts)]


@pytest.fixture(scope="module")
def centred():
    key = jax.random.PRNGKey(3)
    X = jax.random.normal(key, (2500, 800)) + 5.0
    return jax.block_until_ready(X)


def test_factorize_writes_its_phases_in_order(centred, tmp_path):
    # one call first, so that the traced one lowers nothing new
    jax.block_until_ready(api.factorize(centred, 10, K=20, q=2, center=True))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(
            api.factorize(centred, 10, K=20, q=2, center=True))
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    (top,) = [s for s in spans if s[0] == "repro.factorize"]
    inner = [s for s in spans if s[0] != "repro.factorize"]
    assert all(top[1] <= s <= e <= top[2] for _, s, e in inner)
    first = {}
    for name, s, _ in inner:
        first.setdefault(name, s)
    assert [n for n in first if n in PHASES] == PHASES
    assert [n for n, _, _ in inner].count("repro.power_step") == 2
    # the column mean, the sample, two per power step, the projection,
    # and the norm's ||X||^2 and X 1: each a leaf contact, one pass
    assert len(_leaves(inner)) == 9


def test_a_repeat_factorize_lowers_nothing(centred):
    """After the first call, a call with a new key lowers no program
    under any span: the Givens shift update is compiled once a shape."""
    jax.block_until_ready(api.factorize(centred, 10, K=20, q=2, center=True,
                                        key=jax.random.PRNGKey(1)))
    before = obs.counts()
    jax.block_until_ready(api.factorize(centred, 10, K=20, q=2, center=True,
                                        key=jax.random.PRNGKey(2)))
    lowered = {k: v - before.get(k, 0) for k, v in obs.counts().items()
               if k.endswith("/lowered") and v != before.get(k, 0)}
    assert lowered == {}


@pytest.mark.parametrize("blocked, spans, passes", [(False, 3, 2),
                                                     (True, 1, 1)])
def test_a_gram_contact_holds_one_leaf_a_pass(centred, tmp_path, blocked,
                                              spans, passes):
    """The dense Gram contact is two passes, each its own contact inside
    the Gram's span; the blocked one reads each slab once for both."""
    eng = contact.get_engine()
    op = (BlockedOp(ColumnBlockLoader(onp.asarray(centred), block_size=256))
          if blocked else as_linop(centred))
    B = jnp.ones((centred.shape[0], 4), centred.dtype)
    mu = jnp.ones(centred.shape[0], centred.dtype)
    jax.block_until_ready(eng.shifted_gram_matmat(op, B, mu))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(eng.shifted_gram_matmat(op, B, mu))
    finally:
        jax.profiler.stop_trace()
    traced = _host_spans(tmp_path)
    assert [s[0] for s in traced].count("repro.contact") == spans
    assert len(_leaves(traced)) == passes


def test_a_lowering_is_counted_under_its_span_with_one_marker(tmp_path):
    x = jax.block_until_ready(jnp.arange(4.0))
    fresh = jax.jit(lambda v: v * 3.0 + 1.0)
    before = obs.counts()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("repro.test_fresh"):
            jax.block_until_ready(fresh(x))
    finally:
        jax.profiler.stop_trace()
    after = obs.counts()
    assert after.get("repro.test_fresh/lowered", 0) \
        - before.get("repro.test_fresh/lowered", 0) == 1
    spans = _host_spans(tmp_path)
    (outer,) = [s for s in spans if s[0] == "repro.test_fresh"]
    markers = [s for s in spans if s[0] == obs.LOWERED]
    assert len(markers) == 1
    assert outer[1] <= markers[0][1] <= markers[0][2] <= outer[2]


def test_counts_are_kept_with_no_profiler():
    x = jax.block_until_ready(jnp.arange(4.0))
    fresh = jax.jit(lambda v: v - 2.0)
    before = obs.counts()
    with obs.span("repro.test_untraced"):
        jax.block_until_ready(fresh(x))
        jax.block_until_ready(fresh(x))     # cached: counted once
    delta = {k: v - before.get(k, 0) for k, v in obs.counts().items()
             if v != before.get(k, 0)}
    assert all(k.startswith("repro.test_untraced/") for k in delta)
    assert delta["repro.test_untraced/lowered"] == 1
    # compiled, or read from a persistent compilation cache if one is on
    assert delta.get("repro.test_untraced/compiled", 0) \
        + delta.get("repro.test_untraced/cache_loads", 0) == 1
