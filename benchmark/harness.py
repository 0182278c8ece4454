"""The benchmark harness: one cell of ``BENCHMARK.json`` per process.

Everything that belongs to one configuration, traffic mix or metric is
a file found by its name, so a cell, configuration or metric is added
by adding files:

* ``configs/<config>.json``: the configuration as it is run: the system
  adapter (``systems/<system>.py``) that builds it through the program,
  the plain reference (``reference/<reference>.py``), the engine call
  and its budgets, the reference's recorded answer and per-wave counts;
* ``traffic/<traffic>.json``: the parameters of the traffic mix: a
  closed loop of one client re-checking the configuration, and the
  length of the traced stretch;
* ``metrics/<metric>.py``: one reader per metric, ``read(run)`` returning
  a number, or ``None`` where the run holds nothing for it to read. A
  kernel's reader also declares ``KERNEL`` (its name) and
  ``KERNEL_PATTERN`` (a regular expression matching the custom call's
  text in the trace), by which the trace reduction names the kernel.

A run: set-up (imports, the device check, one warm-up check that
builds or fetches the cell's programs), then the measured window
(``--trace 0``: whole checks back to back until ``--seconds`` have
passed) or a profiled stretch of checks (``--trace 1``), then the
comparison with the plain reference that decides ``correct``.
"""

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

import verdict  # noqa: E402


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_file(kind, filename, extra=None):
    """``<kind>/<filename>`` in ``extra`` if it holds one, else in the
    benchmark's own directory."""
    for d in ([extra] if extra else []) + [HERE]:
        path = os.path.join(d, kind, filename)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"benchmark: no {kind}/{filename}")


def bench_spec():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(spec, name):
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload named {name!r} in BENCHMARK.json")


def load_config(name, extra=None):
    return load_json(find_file("configs", f"{name}.json", extra))


def system_module(cfg, extra=None):
    return load_module(find_file("systems", f"{cfg['system']}.py", extra),
                       f"bench_system_{cfg['system']}")


def reference_module(cfg, extra=None):
    return load_module(
        find_file("reference", f"{cfg['reference']}.py", extra),
        f"bench_reference_{cfg['reference']}")


def cell_metrics(spec, cell_name, trace):
    """The metrics a cell reports: its end-to-end metrics untraced, its
    per-layer metrics traced (a metric with ``workloads`` only in the
    cells it lists)."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def load_readers(metrics, extra=None):
    """``{name: reader module}`` of each metric, from
    ``metrics/<name>.py``."""
    return {m["name"]: load_module(
        find_file("metrics", f"{m['name']}.py", extra),
        "bench_metric_" + m["name"].replace(".", "_")) for m in metrics}


def kernel_patterns(readers):
    """``{kernel: pattern}`` the kernel metrics among ``readers`` declare.
    A traced run names the kernels of every per-layer metric, so each
    cell's breakdown names them, also where the cell reads no roofline."""
    return {r.KERNEL: r.KERNEL_PATTERN for r in readers.values()
            if hasattr(r, "KERNEL_PATTERN")}


def read_metrics(metrics, readers, run):
    out = {}
    for m in metrics:
        value = readers[m["name"]].read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- device ----------------------------------------------------------------


def device_info(chips, require_tpu=True):
    """``(devices, kind)``; raises ``SystemExit`` unless JAX finds a TPU
    with at least ``chips`` devices whose kind has a peaks entry."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise SystemExit(
                f"benchmark: JAX found no TPU (platform {dev.platform!r})")
        if len(devices) < chips:
            raise SystemExit(
                f"benchmark: the cell needs {chips} chips, JAX found "
                f"{len(devices)}")
        peaks = load_json(os.path.join(HERE, "peaks.json"))
        if dev.device_kind not in peaks:
            raise SystemExit(
                f"benchmark: device kind {dev.device_kind!r} has no entry "
                "in benchmark/peaks.json")
    return devices[:chips], dev.device_kind


def memory_peak(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- one check -------------------------------------------------------------


def run_check(system, cfg):
    """One check, timed from the builder's spawn call to its verdict:
    the unique count, depth, discovered properties and, where the
    configuration tracks them, the discovery paths. Returns a dict with
    the raw program objects; nothing of the checker outlives it. Its
    stages are host spans in the profiler's trace (next to free when
    no trace is being taken)."""
    from jax.profiler import TraceAnnotation as annotate

    t0 = time.perf_counter()
    try:
        with annotate("verify"):
            with annotate("spawn"):
                builder = system.model(cfg["params"]).checker()
                checker = getattr(builder, cfg["spawn"])(**cfg["engine"])
            with annotate("join"):
                checker.join()
            with annotate("verdict"):
                out = dict(
                    unique=checker.unique_state_count(),
                    depth=checker.max_depth(),
                    discoveries=sorted(checker.discovered_property_names()),
                )
                if cfg["engine"].get("track_paths", True):
                    out["paths"] = {
                        name: path.steps
                        for name, path in checker.discoveries().items()
                    }
        out["wall_s"] = time.perf_counter() - t0
        out["error"] = None
    except Exception as exc:  # a check that raises is a failed check
        out = dict(wall_s=time.perf_counter() - t0,
                   error=f"{type(exc).__name__}: {exc}")
    return out


def closed_loop(run_one, seconds):
    """Whole checks back to back until ``seconds`` have passed; returns
    ``(checks, window_s)``, the window running from the start of the
    first check to the end of the last."""
    checks = []
    t0 = time.perf_counter()
    while True:
        checks.append(run_one())
        if time.perf_counter() - t0 >= seconds:
            break
    return checks, time.perf_counter() - t0


def compile_totals():
    from stateright_tpu.checkers import tpu

    return tpu.compile_ledger_totals()


def _delta(before, after):
    return {k: round(after[k] - before[k], 6)
            for k in ("compiles", "disk_hits", "cold_compiles",
                      "compile_sec", "stage_sec")}


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# -- the run ---------------------------------------------------------------


def run_cell(cell_name, seed, seconds, trace, *, t_start=None,
             require_tpu=True, spec=None, extra=None):
    """Run one cell; returns ``(result, compared)`` where ``result`` is
    the JSON object of the last output line and ``compared`` the numbers
    that decided ``correct``, each beside its limit.

    ``spec`` stands in for ``BENCHMARK.json`` and ``extra`` is a
    directory searched for configurations, traffic mixes, metric
    readers, adapters and references before the benchmark's own (the
    tests use both); ``require_tpu=False`` skips the device check."""
    t_start = time.monotonic() if t_start is None else t_start
    spec = spec or bench_spec()
    cell = find_cell(spec, cell_name)
    cfg = load_config(cell["config"], extra)
    mix = load_json(find_file("traffic", f"{cell['traffic']}.json", extra))
    devices, kind = device_info(cell["chips"], require_tpu)
    system = system_module(cfg, extra)
    metrics = cell_metrics(spec, cell_name, trace)
    readers = load_readers(metrics, extra)

    def run_one():
        return run_check(system, cfg)

    # set-up: imports, the device, and one warm-up check through the
    # window's own call, which builds or fetches every program it uses
    before = compile_totals()
    warm = run_one()
    setup_counters = _delta(before, compile_totals())
    log(f"benchmark: set-up compile ledger {json.dumps(setup_counters)}")
    # building the programs leaves millions of objects behind; collect
    # them now and keep the survivors out of the window's collections
    gc.collect()
    gc.freeze()
    t_window = time.monotonic()
    setup_s = t_window - t_start
    log(f"benchmark: setup_s {setup_s:.3f}, warm-up check "
        f"{warm['wall_s']:.4f} s")

    before = compile_totals()
    reduced = None
    if trace:
        checks = [run_one()]
        traced, window_s, reduced = traced_stretch(
            run_one, mix,
            kernel_patterns(load_readers(spec["per_layer"], extra)))
        checks += traced
        log(f"benchmark: traced {len(traced)} checks; device ops by self "
            "time (label: seconds, count): " + json.dumps(
                {k: v for k, v in sorted(reduced.ops.items(),
                                         key=lambda kv: -kv[1][0])}))
    else:
        checks, window_s = closed_loop(run_one, seconds)
    window_counters = _delta(before, compile_totals())
    log(f"benchmark: window compile ledger {json.dumps(window_counters)}")
    walls = sorted(c["wall_s"] for c in checks)
    log(f"benchmark: {len(walls)} checks in {window_s:.3f} s, wall min "
        f"{walls[0]:.4f} median {walls[len(walls) // 2]:.4f} max "
        f"{walls[-1]:.4f} s")
    peak = memory_peak(devices)
    gc.collect()

    ref_mod = reference_module(cfg, extra)
    judged = verdict.judge(cfg, [warm] + checks, ref_mod, system)
    run = SimpleNamespace(
        cell=cell, config=cfg, traffic=mix, checks=checks,
        ok=judged["ok"][1:], window_s=window_s, setup_s=setup_s,
        counters=dict(setup=setup_counters, window=window_counters),
        trace=reduced, reference=judged["reference"], device_kind=kind,
        peaks=load_json(os.path.join(HERE, "peaks.json")).get(kind),
        traced_checks=(len(checks) - 1) if trace else 0,
    )
    device = dict(platform=devices[0].platform, kind=kind,
                  count=len(devices), memory_peak_bytes=peak)
    result = dict(
        correct=judged["correct"], attempted=1 + len(checks),
        failed=judged["failed"], metrics=read_metrics(metrics, readers, run),
        device=device, seed=seed,
    )
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = reduced.breakdown()
    result["compile"] = dict(setup=setup_counters, window=window_counters)
    result["compared"] = judged["compared"]
    return result, judged["compared"]


def traced_stretch(run_one, mix, kernels):
    """A profiled stretch of whole checks inside one ``window`` span:
    at least ``trace_min_checks`` checks and ``trace_min_seconds``;
    ``kernels`` ({name: pattern}) names the custom calls of its trace."""
    import shutil

    import jax

    import tracereduce

    tdir = os.path.join(HERE, ".trace")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    checks = []
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            while (len(checks) < mix["trace_min_checks"]
                   or time.perf_counter() - t0 < mix["trace_min_seconds"]):
                checks.append(run_one())
        window_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    reduced = tracereduce.reduce_dir(tdir, kernels)
    shutil.rmtree(tdir, ignore_errors=True)
    return checks, window_s, reduced


def main(argv, t_start=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, compared = run_cell(args.workload, args.seed, args.seconds,
                                    bool(args.trace), t_start=t_start)
    except SystemExit as exc:
        print(exc, file=sys.stderr, flush=True)
        return 2
    for name, item in compared.items():
        print(f"compared {name}: {item['value']} (limit {item['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
