"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json`` at the root of the checkout; the traffic file names the
driver (``bench/drivers/<driver>.py``) and each per-layer metric is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a short window.

The run fails, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number compared
with the plain reference beside its limit.  The same numbers are the last
lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    """``(cell, configuration entry, configuration, traffic)`` by name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[cell["config"]]
    config = load_json(os.path.join(ROOT, centry["file"]))
    traffic = load_json(os.path.join(ROOT, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return cell, centry, config, traffic


def metrics_for(entries: list, workload: str) -> list:
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def use_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_description(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(ctx: harness.Context, bench: dict, cell: dict,
             check_device: bool = True) -> dict:
    """Drive the cell and assemble its result object (without printing)."""
    import jax
    if check_device:
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < ctx.chips:
            raise SystemExit(
                f"the cell needs {ctx.chips} TPU chip(s); JAX found "
                f"{len(devs)} {devs[0].platform} device(s)")
    driver = load_module(os.path.join(ROOT, "bench", "drivers",
                                      ctx.traffic["driver"] + ".py"),
                         "bench_driver_" + ctx.traffic["driver"])
    res = driver.run(ctx)
    device = device_description(ctx.chips)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    metrics, breakdown = {}, None
    if ctx.trace:
        from bench import trace as trace_lib
        tr = trace_lib.load(res["trace_path"], chips=ctx.chips)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = tr.breakdown()
        reading = trace_lib.Reading(trace=tr, counters=res["counters"],
                                    device=device, cell=cell,
                                    config=ctx.config, traffic=ctx.traffic)
        for m in metrics_for(bench["per_layer"], ctx.workload):
            reader = load_module(
                os.path.join(ROOT, "bench", "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(res["e2e"], setup_s=res["setup_s"])
        for m in metrics_for(bench["end_to_end"], ctx.workload):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    checks = {name: {"value": v, "limit": lim}
              for name, (v, lim) in res["checks"].items()}
    correct = (res["failed"] == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()))
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["readings"] = res.get("readings", {})
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, config, traffic = find_cell(bench, args.workload)
    use_compile_cache()
    ctx = harness.Context(workload=args.workload, config=config,
                          traffic=traffic, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          chips=cell["chips"], t_start=T_START, log=log)
    out = run_cell(ctx, bench, cell)
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
