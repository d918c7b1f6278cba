#!/usr/bin/env python3
"""Chip benchmark of the scheduler: one cell per call.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its traffic
file ``bench/workloads/<cell>.json`` names the job that drives it
(``bench/jobs/<job>.py``) and the configuration ``bench/configs/<config>.json``
it runs.  With ``--trace 0`` the run measures the cell's end-to-end
metrics over ``--seconds``; with ``--trace 1`` it profiles a short steady
window and reports the per-layer metrics, each read by its own
``bench/metrics/<metric>.py``.  Every run then compares what the timed path
produced with the plain reference (``bench/reference.py``) and prints each
compared number beside its limit.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``--trace 1`` adds ``breakdown``), and
``checks`` last.  Without a TPU, or with fewer chips than the cell asks
for, or without the program's ``src/`` beside ``bench/``, the run prints
no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse           # noqa: E402
import gc                 # noqa: E402
import importlib.util     # noqa: E402
import json               # noqa: E402
import math               # noqa: E402
import pathlib            # noqa: E402
import shutil             # noqa: E402
import sys                # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"            # traces, inside the checkout
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    """Import a harness file by path (metric files have dots in names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """(benchmark spec, cell entry, traffic file, configuration file)."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    traffic = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    return spec, cell, traffic, cfg


def metric_names(spec: dict, cell: str, kind: str) -> list[str]:
    """The metrics of ``kind`` ('end_to_end' or 'per_layer') that the cell
    reports: those without a ``workloads`` list, and those listing it."""
    return [m["name"] for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def units(spec: dict) -> dict:
    return {m["name"]: m["unit"] for k in ("end_to_end", "per_layer")
            for m in spec[k]}


class Run:
    """What a per-layer metric's reader sees: the reduced trace and the
    job's counters."""

    def __init__(self, trace, counters: dict):
        self.trace, self.counters = trace, counters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, traffic, cfg = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench/run.py needs the program's src/repro beside bench/ "
            f"(looked in {ROOT})")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} TPU chip(s); jax sees "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    # every program, however quick to compile, is cached: set-up then
    # does the same work on every run after a checkout's first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **_: compiles.__setitem__(0, compiles[0] + 1)
        if ev == COMPILE_EVENT else None)
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")

    job = load_module(BENCH / "jobs" / f"{traffic['job']}.py").Job(
        cfg, traffic, args.seed, cell["chips"], args.seconds,
        bool(args.trace), log)
    job.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s:.3f} ({compiles[0]} compiles)")
    before = compiles[0]
    trace = None
    if args.trace:
        tdir = OUT / f"trace_{args.workload}"
        shutil.rmtree(tdir, ignore_errors=True)
        out = job.traced(str(tdir))
        from reduce_trace import load   # bench/ is on sys.path
        trace = load(str(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        out = job.window()
    log(f"compiles inside the window: {compiles[0] - before}")
    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    log(f"peak_bytes_in_use per device: {peaks}")
    job.release()
    gc.collect()
    checks = job.check()

    unit = units(spec)
    if args.trace:
        run = Run(trace, out["counters"])
        values = {}
        for name in metric_names(spec, args.workload, "per_layer"):
            v = load_module(BENCH / "metrics" / f"{name}.py").read(run)
            if v is not None:
                values[name] = v
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        values = {k: values[k] for k in
                  metric_names(spec, args.workload, "end_to_end")}
    metrics = {k: {"value": v, "unit": unit[k]} for k, v in values.items()}
    correct = all(v is not None and lim is not None and math.isfinite(v)
                  and v <= lim
                  for _, v, lim in checks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(p or 0 for p in peaks)}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace.mean_busy_s(), window_s=trace.window_s())
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.gaps_by_host(10)}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        log(f"check {n} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
