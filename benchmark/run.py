#!/usr/bin/env python3
"""benchmark/run.py — run ONE cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell ``<config>.<traffic>`` is found by name: ``BENCHMARK.json`` names the
configuration's file, the traffic mix is ``benchmark/traffic/<traffic>.json``,
its ``kind`` picks ``benchmark/runners/<kind>.py``, and every per-layer metric
is read by ``benchmark/metrics/<name>.py``. Nothing here names a cell, a
configuration or a traffic mix.

The LAST stdout line is the result object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``); the set-up
split, the compile cache's hits and misses, the whole-window throughput and
the request table go on earlier lines. Without a TPU the run fails unless
``--rehearse`` is given; a rehearsal runs tiny sizes on the CPU, says
``"platform": "cpu"`` and carries no device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# metric sources that a CPU rehearsal may still report: counts made by the
# program, never a time, a rate or a share of the device
REHEARSAL_SOURCES = ("program_counter",)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys; nested dicts merge one level down."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def find_cell(bench: dict, name: str, rehearse: bool):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, conf_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    return cell, config, traffic


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_peaks(device_kind: str) -> dict:
    path = os.path.join(HERE, "peaks", device_kind.replace(" ", "_") + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"device kind {device_kind!r} is not in the peaks "
                         f"table (benchmark/peaks/): refusing to guess")
    return load_json(path)


def read_metric(name: str, obs: dict):
    """One per-layer metric from its own reader; None when its source is
    absent in this run."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="measured window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: checks the harness, "
                         "reports no device metric")
    ap.add_argument("--sweep", default=None,
                    help="serving only: comma-separated request rates; one "
                         "window each, a table line each, the last one is "
                         "the result")
    ap.add_argument("--dump-events", default=None,
                    help="traced run: also write the reduced trace events "
                         "as JSON to this path")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, args.workload, args.rehearse)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    t_import = time.perf_counter()
    import jax

    from benchmark import harness

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"benchmark: jax found platform {dev.platform!r}, not a TPU — "
              f"refusing to measure (--rehearse walks the harness through "
              f"on the CPU)", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} chips, "
              f"jax found {len(devices)}", file=sys.stderr)
        return 3
    peaks = load_peaks(dev.device_kind) if on_tpu else None

    clock = harness.SetupClock(T_PROCESS, devices[:cell["chips"]] if on_tpu else ())
    clock.mark("import", since=t_import)
    counters = harness.CompileCounters.install()
    emit({"line": "start", "workload": cell["name"], "seed": args.seed,
          "seconds": seconds, "trace": args.trace, "rehearse": args.rehearse,
          "platform": dev.platform, "device_kind": dev.device_kind,
          "device_count": len(devices), "jax": jax.__version__,
          "compile_cache_dir": counters.cache_dir})

    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=seconds, trace=bool(args.trace), rehearse=args.rehearse,
        on_tpu=on_tpu, devices=devices[:cell["chips"]], peaks=peaks,
        clock=clock, counters=counters, emit=emit, root=ROOT,
        sweep=[float(r) for r in args.sweep.split(",")] if args.sweep else None,
        dump_events=args.dump_events)
    runner = importlib.import_module(f"benchmark.runners.{traffic['kind']}")
    obs = runner.run(ctx)  # the observation: end-to-end values, spans, counters, trace

    emit({"line": "setup", "setup_s": obs["end_to_end"]["setup_s"],
          "split_s": clock.split(), **counters.summary(),
          "peak_bytes_after": clock.peak_bytes_after()})

    metrics = {}
    group = "per_layer" if args.trace else "end_to_end"
    for m in bench[group]:
        if not applies(m, cell["name"]):
            continue
        if args.rehearse and m["source"] not in REHEARSAL_SOURCES:
            continue
        value = (read_metric(m["name"], obs) if args.trace
                 else obs["end_to_end"].get(m["name"]))
        if value is None:
            if args.trace:
                continue  # source absent in this cell: left out, not 0
            raise SystemExit(f"cell {cell['name']} did not produce "
                             f"end-to-end metric {m['name']}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"]}
    if on_tpu:
        device["memory_peak_bytes"] = harness.memory_peak_bytes(ctx.devices)
    result = {"correct": bool(obs["correct"]), "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": metrics,
              "device": device}
    reduced = obs.get("trace")
    if on_tpu and args.trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
