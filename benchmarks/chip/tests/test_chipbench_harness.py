"""The harness finds every file by name, and refuses to run off the
chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import harness

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.load_cell(cell)
    assert (harness.HERE / "data" / f"{c.config['generator']}.py").is_file()
    assert (harness.HERE / "traffic"
            / f"{c.traffic['driver']}.py").is_file()
    assert set(c.workload["limits"]) >= {"sv_gap", "cert_short"}
    names = {m["name"] for m in c.end_to_end}
    assert {"factorize_ms", "peak_hbm_gib", "setup_s"} <= names
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        reader = harness.load_module(harness.HERE / "metrics"
                                     / f"{m['name']}.py")
        assert callable(reader.read)


def test_new_cell_and_metric_found_by_name(tmp_path):
    """A cell, a traffic mix and a metric added as files (and entries in
    BENCHMARK.json) are found with no edit to any existing file."""
    base = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (base / "traffic" / "q0_nocentre.json").write_text(json.dumps(
        {"driver": "closed_loop_factorize", "k": 10, "K": 20, "q": 0,
         "center": False, "warmup": 1}))
    (base / "workloads" / "lfw_faces.q0_nocentre.json").write_text(
        json.dumps({"check_jobs": 1, "limits": {"sv_gap": 1e-3}}))
    (base / "metrics" / "jobs_traced.new.py").write_text(
        "def read(summary, info):\n    return float(summary['jobs'])\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(
        {"name": "lfw_faces.q0_nocentre", "config": "lfw_faces",
         "traffic": "q0_nocentre", "chips": 1, "why": "test"})
    bench["workloads"].append(
        {"name": "lfw_faces.q2_again", "config": "lfw_faces",
         "traffic": "q2", "chips": 1, "why": "test"})
    (base / "workloads" / "lfw_faces.q2_again.json").write_text(
        json.dumps({"check_jobs": 1, "limits": {"sv_gap": 1e-3}}))
    bench["per_layer"].append(
        {"name": "jobs_traced.new", "unit": "jobs", "better": "higher",
         "source": "device_trace", "layer": "device",
         "moves": "factorize_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("lfw_faces.q0_nocentre", root=tmp_path)
    assert cell.traffic["q"] == 0 and cell.config["m"] == 62500
    assert [m["name"] for m in cell.per_layer] == ["device_idle_share",
                                                   "jobs_traced.new"]
    reader = harness.load_module(base / "metrics" / "jobs_traced.new.py")
    assert reader.read({"jobs": 3}, {}) == 3.0
    # the metric without a workloads list reaches every cell that
    # reports what it moves
    other = harness.load_cell("lfw_faces.q2_again", root=tmp_path)
    assert "jobs_traced.new" in [m["name"] for m in other.per_layer]


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "lfw_faces.q2", "--seed", "3", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_root_key_takes_large_seeds():
    a = harness.root_key(2 ** 31 + 12345)
    b = harness.root_key(12345)
    assert a.shape == b.shape and bool((a != b).any())
    assert bool((harness.root_key(2 ** 40) == harness.root_key(2 ** 40))
                .all())


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_sampled_outputs_are_kept_on_the_host():
    """The closed loop's sample of whole outputs holds no device memory,
    and the time its copies take is reported."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip.data import Dataset
    from benchmarks.chip.reference import Factors, Report

    driver = harness.load_module(harness.HERE / "traffic"
                                 / "closed_loop_factorize.py")

    def program(x, k, *, key, K, q, center):
        U = x[:, :k] * jax.random.normal(key, ())
        return Factors(U, jnp.ones(k), U.T), Report(jnp.sum(x))

    ds = Dataset(operand=jnp.ones((8, 6)), kwargs={}, dense=None)
    log = driver.run(program, ds,
                     {"k": 2, "K": 4, "q": 1, "center": True, "warmup": 1},
                     key=jax.random.PRNGKey(0), seed=5, seconds=0.2,
                     keep=3, annotate=lambda j: contextlib.nullcontext())
    assert log["attempted"] >= 3 and len(log["kept"]) == 3
    for _, _, out in log["kept"]:
        assert all(isinstance(a, np.ndarray)
                   for a in jax.tree_util.tree_leaves(out))
    assert log["kept_copy_s"] > 0.0
    assert set(log["metrics"]) == {"factorize_ms"}
