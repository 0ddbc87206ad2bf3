"""Run one cell of the benchmark once.

    python3 bench/cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``) and its traffic
(``bench/traffic/<traffic>.json``); what belongs to the pair, such as the
limits of the comparison with the reference or a rate set from this
configuration's capacity, is in ``bench/cells/<workload>.json`` and is laid
over the traffic's parameters. The traffic file's ``kind`` names the
driver (``bench/drivers/<kind>.py``); each metric is read by
``bench/metrics/<metric>.py``. With ``--trace 0`` the cell's end-to-end
metrics are printed, with ``--trace 1`` its per-layer metrics, read in a
run with the profiler on.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. The last line of standard output is the result; the last
lines of standard error are the numbers compared with the plain reference,
each beside its limit.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402
from harness import ROOT, NoChip, Run, load_json  # noqa: E402


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metrics this cell reports: end-to-end ones in a plain run,
    per-layer ones in a traced run; a metric without ``workloads`` is every
    cell's."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def make_run(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float) -> Run:
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(by_name)}")
    wl = by_name[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     wl["traffic"] + ".json"))
    traffic.update(load_json(os.path.join(BENCH_DIR, "cells",
                                          workload + ".json")))
    return Run(workload=wl, config=config, traffic=traffic, seed=seed,
               seconds=seconds, trace=trace, t_process=t_process)


def report(r: Run, spec: dict, devices, peaks: dict) -> str:
    metrics = {}
    for m in cell_metrics(spec, r.workload["name"], r.trace):
        value = load_module("metrics", m["name"]).read(r, peaks)
        if value is not None:
            metrics[m["name"]] = (value, m["unit"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": r.memory_peak_bytes}
    breakdown = None
    if r.trace:
        device["busy_s"] = r.device_trace["busy_s"]
        device["window_s"] = r.device_trace["window_s"]
        breakdown = {k: r.device_trace[k]
                     for k in ("device_ops", "idle_gaps")}
    return harness.result_line(r, metrics, device, breakdown)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    r = make_run(spec, args.workload, args.seed, args.seconds,
                 bool(args.trace), T_PROCESS)
    try:
        devices = harness.require_chips(r.workload["chips"])
    except NoChip as e:
        print(f"cell: {e}; refusing to run on another backend",
              file=sys.stderr)
        return 2
    peaks = harness.peaks_for(devices[0].device_kind)
    r.note(device_kind=devices[0].device_kind, device_count=len(devices),
           workload=args.workload, seed=args.seed,
           compile_cache=harness.enable_compile_cache())
    r.counter = harness.CompileCounter().install()
    log_dir = None
    if r.trace:
        import devtrace

        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        r.profiler = devtrace.Profiler(log_dir)
    try:
        driver = load_module("drivers", r.traffic["kind"])
        driver.run(r, devices)
        if r.trace:
            r.device_trace = r.profiler.result()
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    r.note(**{"setup_s": r.setup_s, "window_s": r.window_s,
              "attempted": r.attempted, "failed": r.failed,
              **r.counter.summary(), **r.counters})
    line = report(r, spec, devices, peaks)
    harness.emit_limits(r.limits)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
