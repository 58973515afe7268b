#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process per run.  It finds the cell in `BENCHMARK.json`, reads the
cell's configuration, traffic mix and per-layer metric files by name, builds
the system under test from the seed, warms up every shape the cell's traffic
reaches (set-up), checks correctness outside the window, measures for
`--seconds`, and prints the contract's JSON object as the last line of
stdout.  Notes go to stderr; its last lines, and the result's last key
`checks`, are each number `correct` compared beside its limit.  With
`--trace 0` the metrics are the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics.

A `workloads` cell needs a TPU with at least the cell's chips and fails
without one; nothing here falls back to the CPU.  Only the rehearsal cells of
`benchmark/testdata/cells.json` (gpt-tiny, never listed in `workloads`) run
on the CPU, and they print `"platform": "cpu"`.
"""
from __future__ import annotations

import time

_T_START = time.monotonic()        # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
sys.path.insert(0, ROOT)

from benchmark.checks import held  # noqa: E402


def say(msg: str):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def _load(path: str):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(manifest: dict, name: str) -> dict:
    """The cell with its files resolved: a `workloads` entry of the manifest,
    or a CPU rehearsal cell of `testdata/cells.json`."""
    for c in manifest["workloads"]:
        if c["name"] == name:
            conf = next(k for k in manifest["configs"]
                        if k["name"] == c["config"])
            return dict(c, config_file=conf["file"], platform="tpu",
                        traffic_file=f"benchmark/traffic/{c['traffic']}.json",
                        metrics_as=name)
    for c in _load("benchmark/testdata/cells.json"):
        if c["name"] == name:
            return dict(
                c, platform="cpu", metrics_as=c["like"],
                config_file=f"benchmark/testdata/{c['config']}.json",
                traffic_file=f"benchmark/testdata/{c['traffic']}.json")
    raise SystemExit(f"benchmark: no cell named {name!r} in BENCHMARK.json "
                     f"or benchmark/testdata/cells.json")


class CompileLog:
    """What XLA compiled and what the persistent cache served, from
    `jax.monitoring`.  `requests` counts every program the process needed
    built or loaded; it must not move inside a measured window."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration


class Ctx:
    """What a driver gets: the cell, its files' contents, the arguments."""

    def __init__(self, cell, config, mix, args, devices, log):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.devices, self.log = devices, log
        self.t_start = _T_START
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        self.say = say
        self.host = None                  # a serve driver's `host.delta`

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def stop_trace(self):
        import jax
        jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = _load("BENCHMARK.json")
    cell = find_cell(manifest, args.workload)
    config = _load(cell["config_file"])
    mix = _load(cell["traffic_file"])
    if cell["platform"] == "cpu":
        # rehearsal cells only: gpt-tiny on the CPU, virtual devices for a mesh
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell['chips']}")

    import jax
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # keep every program, however quick to compile: a later run of this cell
    # then loads all of them (the default keeps only those over 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # source locations without the checkout's path: a Pallas kernel carries
    # its own into the cache key, so that a checkout elsewhere sharing the
    # cache directory would compile every program with a kernel again
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(ROOT + os.sep))
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != cell["platform"] or len(devices) < cell["chips"]:
        say(f"cell {cell['name']} needs {cell['chips']} x {cell['platform']}"
            f"; JAX reports {len(devices)} x {d0.platform} ({d0.device_kind})"
            f". Nothing is measured and nothing falls back.")
        return 1
    log = CompileLog()
    say(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace}; {len(devices)} x {d0.device_kind}; compile cache "
        f"{cache_dir}")

    ctx = Ctx(cell, config, mix, args, devices[:cell["chips"]], log)
    driver = importlib.import_module(f"benchmark.{config['kind']}_driver")
    res = driver.run(ctx)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in ctx.devices)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    obs = dict(res["observations"], memory_peak_bytes=peak,
               device_kind=d0.device_kind, chips=cell["chips"], config=config)
    # the driver's `correct` is `held` of its own `checks`; a closed loop's
    # supply is the run's, judged here and only here
    # (serve_driver.supply_check)
    supply = res.get("supply", {})
    out = {"correct": bool(res["correct"]) and held(supply),
           "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    like = cell["metrics_as"]
    if not ctx.trace:
        values = dict(res["end_to_end"], setup_s=res["setup_s"])
        for m in manifest["end_to_end"]:
            if applies(m, like):
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    else:
        from benchmark import trace_reduce
        path = trace_reduce.find_xplane(ctx.trace_dir)
        red = trace_reduce.reduce_trace(path) if path else None
        if red is None:
            say("no device plane in the trace: device metrics are left out")
        else:
            device["busy_s"], device["window_s"] = (red["busy_s"],
                                                    red["window_s"])
            out["breakdown"] = trace_reduce.breakdown(red)
        obs["trace"] = red
        for m in manifest["per_layer"]:
            if not applies(m, like):
                continue
            spec = _load(f"benchmark/metrics/{m['name']}.json")
            mod, fn = spec["reader"].rsplit(".", 1)
            reader = getattr(importlib.import_module(f"benchmark.{mod}"), fn)
            value = reader(obs, **spec.get("args", {}))
            if value is None:
                say(f"metric {m['name']}: nothing to read, left out")
            else:
                out["metrics"][m["name"]] = {"value": float(value),
                                             "unit": m["unit"]}
    say(f"set-up {res['setup_s']:.1f}s (compiling or loading programs "
        f"{res['setup_compile_s']:.1f}s; cache hits {res['setup_hits']} of "
        f"{res['setup_requests']}); notes {json.dumps(res['notes'])}")
    if ctx.host:
        say(f"host over the window {json.dumps(ctx.host)}")
    # each number `correct` compared, beside its limit: the last lines of
    # stderr and the last key of the result
    out["checks"] = dict(res["checks"], **supply)
    for k, c in out["checks"].items():
        say(f"check {k}: {c['value']} {c['holds']} {c['limit']}" +
            (f" -- {c['why']}" if c.get("why") else ""))
    say(f"correct {out['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
