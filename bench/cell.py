"""Run one benchmark cell once and print its result.

    python3 bench/cell.py --workload drcgnn-large-resident --seed 7 \
        --seconds 30 --trace 0

Everything is found by name: the cell in ``BENCHMARK.json`` and
``bench/workloads/<cell>.json``, its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` (whose ``kind`` names the driver,
``bench/drivers/<kind>.py``, which runs the window and makes its own
comparison with the plain reference), and every metric in
``bench/metrics/<metric>.py``.  The numbers the driver reads are judged
against the limits in the cell's file, whatever they are.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of the
window.

The run needs the accelerator: where JAX finds none, or fewer chips than
the cell asks for, it exits non-zero and prints no result.  The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks``, each compared number beside its limit); the compared
numbers are also the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_driver(kind: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_driver_{kind}", os.path.join(BENCH, "drivers", f"{kind}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The entries of ``section`` this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def judge(read: dict, limits: dict):
    """(correct, [(name, value, limit)]) for whatever numbers the driver
    read: correct when every limited number is there, finite and at or
    under its limit."""
    rows = [(k, float(read.get(k, math.nan)), float(lim))
            for k, lim in limits.items()]
    ok = bool(rows) and all(math.isfinite(v) and v <= lim
                            for _, v, lim in rows)
    return ok, rows


def check_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator; this benchmark does not run "
                     "on the CPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chip(s), the cell asks for {chips}")
    return devs


def enable_cache() -> str:
    """JAX's persistent compilation cache: JAX_COMPILATION_CACHE_DIR where
    set, else the fixed directory ``.jax_cache`` in the checkout.  Every
    program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, trace_dir: str = None,
             overrides: dict = None) -> dict:
    """One run; returns the result object.  ``overrides`` replaces entries
    of the configuration and the traffic (the CPU tests shrink the cell)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    entry = cells[workload]
    wl = load_json(BENCH, "workloads", f"{workload}.json")
    cfg = load_json(BENCH, "configs", f"{entry['config']}.json")
    traffic = load_json(BENCH, "traffic", f"{entry['traffic']}.json")
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))

    if require_chip:
        devs = check_chip(entry["chips"])
    else:
        import jax
        devs = jax.devices()
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    if require_chip:
        log(f"compile cache: {enable_cache()}")

    tmp = None
    if trace and not trace_dir:
        tmp = trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        driver = load_driver(traffic["kind"])
        ctx = driver.run(BENCH, cfg, traffic, seed, seconds,
                         trace_dir if trace else None, T_START, log=log)
        reduced = None
        if trace:
            sys.path.insert(0, BENCH)
            import trace_reduce
            from jax.profiler import ProfileData
            reduced = trace_reduce.reduce(ProfileData.from_file(
                trace_reduce.find_xplane(trace_dir)))
            log(f"trace: {reduced['n_events']} device events on "
                f"{reduced['n_devices']} device(s)")
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    peaks = load_json(BENCH, "peaks.json")["devices"]
    kind = devs[0].device_kind
    if require_chip and kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    ctx.update(cfg=cfg, traffic=traffic, trace=reduced,
               peak=peaks.get(kind))

    sys.path.insert(0, BENCH)
    import readers
    read = ctx["readings"]
    correct, rows = judge(read, wl["limits"])
    correct = (correct and ctx["compiles_in_window"] == 0
               and ctx["attempted"] > 0)
    log("readings: " + json.dumps(read))

    metrics = {}
    section = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(bench, workload, section):
        value = readers.load(m["name"]).read(ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = dict(platform=devs[0].platform, kind=kind, count=len(devs),
                  memory_peak_bytes=ctx["memory_peak_bytes"])
    result = dict(correct=bool(correct), attempted=ctx["attempted"],
                  failed=ctx["failed"], metrics=metrics, device=device)
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    for name, v, lim in rows:
        log(f"{name} {v!r} limit {lim!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace in this directory")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), trace_dir=args.trace_dir)
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
