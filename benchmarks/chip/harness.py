"""One run of one cell: set up, time the window, check, report.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is found by name: the cell's entry in the checkout's
``BENCHMARK.json`` names its configuration (``configs/<name>.json``,
whose ``generator`` is a module in ``data/``) and its traffic mix
(``traffic/<name>.json``, whose ``driver`` is a module in
``traffic/``); ``workloads/<cell>.json`` holds the cell's check
settings and limits; each per-layer metric is read by
``metrics/<metric>.py``.  Adding any of these is adding files.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: Run outputs (traces) in the checkout, gitignored.
OUT = ROOT / ".chipbench"
GIB = 2.0 ** 30


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file found by name (names may hold dots)."""
    name = "chipbench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, bench: dict | None = None, *,
              root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``bench`` (default: the checkout's
    ``BENCHMARK.json``), with every file it names read from ``root``."""
    base = root / HERE.relative_to(ROOT)
    bench = bench if bench is not None else _load_json(
        root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moves)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_load_json(root / conf["file"]),
                traffic=_load_json(base / "traffic"
                                   / f"{w['traffic']}.json"),
                workload=_load_json(base / "workloads" / f"{name}.json"),
                end_to_end=e2e, per_layer=layer)


def peaks_for(kind: str) -> dict:
    table = _load_json(HERE / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one, with every
    program cached (small ones too), so that only the first run of a
    cell in a checkout compiles."""
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts lowerings, backend compiles and compile-cache reads."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled",
              "/jax/compilation_cache/cache_hits": "cache_loads"}

    def __init__(self):
        import jax.monitoring as mon

        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on)

    def _on(self, event, *args, **kwargs):
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def root_key(seed: int):
    """The run's PRNG key from a seed of any size."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              (seed // 2 ** 31) % 2 ** 31)


def device_info(devices) -> dict:
    d = devices[0]
    peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_proc: float, require_tpu: bool = True, program=None,
             cell: Cell | None = None, log=print) -> dict:
    """One run; returns the result object (the last stdout line)."""
    import time

    import jax

    from benchmarks.chip import check, tracefile

    cell = cell if cell is not None else load_cell(name)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} "
                     "device(s)")
    peaks = peaks_for(devices[0].device_kind) if require_tpu else None
    counter = CompileCounter()
    before = counter.snapshot()
    if require_tpu:
        use_compile_cache()
    devices = devices[:cell.chips]
    log(f"device platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__}")
    if program is None:
        from repro import api
        program = api.factorize
    key = root_key(seed)
    gen = load_module(HERE / "data" / f"{cell.config['generator']}.py")
    ds = gen.make(cell.config, jax.random.fold_in(key, 0), seed, devices)
    driver = load_module(HERE / "traffic"
                         / f"{cell.traffic['driver']}.py")
    trace_dir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)

    warm, marks = {}, {}

    def on_warm(i):
        warm[i] = counter.snapshot()

    @contextlib.contextmanager
    def annotate(j):
        if j == 0:
            # set-up ends where the first timed job starts
            marks["setup_s"] = time.perf_counter() - t_proc
            marks["at_window"] = counter.snapshot()
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
        with jax.profiler.TraceAnnotation(tracefile.JOB_SPAN):
            yield

    log_ = driver.run(program, ds, cell.traffic,
                      key=jax.random.fold_in(key, 1), seed=seed,
                      seconds=seconds,
                      keep=int(cell.workload["check_jobs"]),
                      annotate=annotate, on_warm=on_warm)
    if trace:
        jax.profiler.stop_trace()
    after = counter.snapshot()
    n_warm = cell.traffic["warmup"]
    last_warm = _delta(warm.get(n_warm - 2, before),
                       warm.get(n_warm - 1, before))
    log(f"compiles in_window={_delta(marks['at_window'], after)} "
        f"in_last_warmup={last_warm} "
        f"in_setup={_delta(before, marks['at_window'])}")
    dev = device_info(devices)
    done = len(log_["summaries"])
    lat = log_["latencies"]
    log(f"window jobs={log_['attempted']} ok={done} "
        f"window_s={log_['window_s']!r} setup_s={marks['setup_s']!r} "
        f"kept_copy_s={log_.get('kept_copy_s')!r}")
    for e in log_["errors"][:5]:
        log(f"job error: {e}")

    if not done:
        raise RuntimeError(f"no job of {cell.name} completed in the window")
    result_metrics = {}
    if not trace:
        # the traffic driver measures what its traffic defines; the
        # harness adds what every cell reports
        values = dict(log_["metrics"],
                      peak_hbm_gib=dev["memory_peak_bytes"] / GIB,
                      setup_s=marks["setup_s"])
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"{cell.traffic['driver']} measures no "
                               f"{m['name']!r}; it has {sorted(values)}")
            result_metrics[m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    # the program's state is freed before the reference runs
    summaries, kept = log_["summaries"], log_["kept"]
    del log_
    Xbar = check.target(ds, cell.traffic)
    del ds          # X itself: the reference reads the centred copy
    checks, bad_jobs, readings = check.compare(Xbar, cell, summaries, kept)
    del Xbar
    failed = (len(lat) - done) + bad_jobs
    checks["failed_jobs"] = (float(failed), 0.0)
    correct = all(v <= lim for v, lim in checks.values())

    out = {"correct": bool(correct), "attempted": len(lat),
           "failed": int(failed), "metrics": result_metrics,
           "device": dev}
    if trace:
        summary = tracefile.summarize(
            tracefile.find_xplane(trace_dir),
            [d.id for d in devices])
        tracefile.save(summary, trace_dir / "summary.json.gz")
        info = {"cell": cell, "peaks": peaks}
        for m in cell.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            v = reader.read(summary, info)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        w = summary["window"]
        busy = [tracefile.busy_ns(ops, w)
                for ops in summary["devices"].values()]
        dev["busy_s"] = sum(busy) / len(busy) * 1e-9
        dev["window_s"] = (w[1] - w[0]) * 1e-9
        out["breakdown"] = tracefile.breakdown(summary)
    out["readings"] = readings
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, (v, lim) in checks.items()}
    return out
