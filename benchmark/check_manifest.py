#!/usr/bin/env python3
"""Validate `BENCHMARK.json` and every file it names against the benchmark
contract.  Exit code 1 with the offending key on the first line of stderr.

    python3 benchmark/check_manifest.py

Run it before the first chip call and last thing before finishing: a
manifest the driver refuses costs the whole PR (PR 22 was refused over a
`layer` with spaces in it).
"""
from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"host_clock", "device_trace"}
DATA_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
LAYERS = ("gateway", "engine", "train-step", "mesh", "kernels", "device")
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state_size", "proj",
               "head_size", "expansion", "experts_per")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
MAX_CELLS = 24


class Bad(Exception):
    pass


def read_json(path):
    with open(path) as f:
        return json.load(f)


def need(cond, key, msg):
    if not cond:
        raise Bad(f"{key}: {msg}")


def line(s, key, lo=1, hi=200):
    need(isinstance(s, str) and lo <= len(s) <= hi and "\n" not in s and
         "\t" not in s and "\r" not in s, key,
         f"must be {lo} to {hi} characters on one line with no tab")


def name(s, key):
    need(isinstance(s, str) and NAME.match(s), key,
         "must be 1 to 64 of letters, digits, '_', '.', '-', starting with "
         f"a letter, a digit or '_' (got {s!r})")


def keys(d, key, required, optional=()):
    need(isinstance(d, dict), key, "must be an object")
    extra = set(d) - set(required) - set(optional)
    missing = set(required) - set(d)
    need(not extra and not missing, key,
         f"keys must be exactly {sorted(required)} (+ {sorted(optional)}); "
         f"extra {sorted(extra)}, missing {sorted(missing)}")


def inside(path, roots):
    p = os.path.normpath(path)
    return any(p == r or p.startswith(r.rstrip("/") + "/") for r in roots)


def is_width(k: str) -> bool:
    k = k.lower()
    return (k.endswith("_dim") or k.endswith("_rank") or
            any(w in k for w in WIDTH_WORDS))


def check(root: str = ROOT) -> None:
    with open(os.path.join(root, "BENCHMARK.json"), "rb") as f:
        raw = f.read()
    need(len(raw) <= 64 * 1024, "BENCHMARK.json", "larger than 64 KiB")
    m = json.loads(raw)
    keys(m, "BENCHMARK.json", TOP)

    paths = m["paths"]
    need(isinstance(paths, list) and 1 <= len(paths) <= 16, "paths",
         "1 to 16 directories")
    for p in paths:
        need(isinstance(p, str) and PATH.match(p) and not p.startswith("/")
             and ".." not in p.split("/"), "paths", f"bad directory {p!r}")
        need(os.path.isdir(os.path.join(root, p)), "paths",
             f"{p} is not a directory")
        for d, _, files in os.walk(os.path.join(root, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), root)
                need(re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), "paths",
                     f"file name {rel!r} has characters outside a name's")
    roots = [os.path.normpath(p) for p in paths]

    cmd = m["command"]
    need(isinstance(cmd, list) and 1 <= len(cmd) <= 32, "command",
         "a list of at most 32 strings")
    for w in cmd:
        line(w, "command")
        need(not w.startswith("/") and ".." not in w.split("/"), "command",
             f"{w!r} starts with '/' or leads out through '..'")
        if os.path.exists(os.path.join(root, w)) and "/" in w:
            need(inside(w, roots), "command",
                 f"{w!r} is a file of the repo outside paths")

    rs = m["run_seconds"]
    need(isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= 51,
         "run_seconds", "a whole number from 1 to 51")
    need((2 + 14 * MAX_CELLS) * (rs + 60) + MAX_CELLS * 180 + 1200 <= 43200,
         "run_seconds", "a full check of 24 cells does not fit 43200 s")

    configs = m["configs"]
    need(isinstance(configs, list) and 1 <= len(configs) <= 24, "configs",
         "1 to 24 configurations")
    seen_files, cnames = set(), set()
    for c in configs:
        k = f"configs[{c.get('name')}]"
        keys(c, k, {"name", "source", "file", "reduced", "why"})
        name(c["name"], k + ".name")
        need(c["name"] not in cnames, k, "duplicate name")
        cnames.add(c["name"])
        line(c["source"], k + ".source")
        line(c["why"], k + ".why")
        need(inside(c["file"], roots) and PATH.match(c["file"]), k + ".file",
             "must lie under paths")
        need(c["file"] not in seen_files, k + ".file",
             "is another configuration's file")
        seen_files.add(c["file"])
        need(os.path.isfile(os.path.join(root, c["file"])), k + ".file",
             f"{c['file']} does not exist")
        body = read_json(os.path.join(root, c["file"]))
        need(isinstance(body, dict), k + ".file", "must hold a JSON object")
        red = c["reduced"]
        need(isinstance(red, list) and len(red) <= 16, k + ".reduced",
             "a list of at most 16 keys")
        for r in red:
            name(r, k + ".reduced")
            need(not is_width(r), k + ".reduced",
                 f"{r!r} names a width; widths are never cut")
            need(r in body, k + ".reduced", f"{r!r} is not a key of the file")
        need(body.get("reduced", red) == red, k + ".reduced",
             "differs from the file's own 'reduced'")

    cells = m["workloads"]
    need(isinstance(cells, list) and 1 <= len(cells) <= MAX_CELLS,
         "workloads", "1 to 24 cells")
    wnames, pairs = set(), set()
    for w in cells:
        k = f"workloads[{w.get('name')}]"
        keys(w, k, {"name", "config", "traffic", "chips", "why"})
        for f in ("name", "config", "traffic"):
            name(w[f], f"{k}.{f}")
        need(w["name"] not in wnames, k, "duplicate name")
        wnames.add(w["name"])
        need(w["config"] in cnames, k + ".config", "no such configuration")
        need((w["config"], w["traffic"]) not in pairs, k,
             "this pair of configuration and traffic appears twice")
        pairs.add((w["config"], w["traffic"]))
        need(w["chips"] in (1, 4), k + ".chips", "1 or 4")
        line(w["why"], k + ".why")
        files = [f for f in os.listdir(os.path.join(root, "benchmark",
                                                    "traffic"))
                 if os.path.splitext(f)[0] == w["traffic"]]
        need(len(files) == 1 and files[0].endswith(DATA_EXT), k + ".traffic",
             f"needs one data file benchmark/traffic/{w['traffic']}.*")
    four = sum(1 for w in cells if w["chips"] == 4)
    need(four <= max(1, len(cells) // 4), "workloads",
         f"{four} four-chip cells of {len(cells)}: at most 25%, and one")
    used = {w["config"] for w in cells}
    need(used == cnames, "configs",
         f"not used by any cell: {sorted(cnames - used)}")

    def cells_of(metric, k):
        if "workloads" not in metric:
            return set(wnames)
        ws = metric["workloads"]
        need(isinstance(ws, list) and ws and set(ws) <= wnames,
             k + ".workloads", "must list existing cells")
        return set(ws)

    mnames, e2e_cells = set(), {}
    e2e = m["end_to_end"]
    need(isinstance(e2e, list) and 1 <= len(e2e) <= 16, "end_to_end",
         "1 to 16 metrics")
    for x in e2e:
        k = f"end_to_end[{x.get('name')}]"
        keys(x, k, {"name", "unit", "better", "bound", "source"},
             {"workloads"})
        name(x["name"], k + ".name")
        need(x["name"] not in mnames, k, "duplicate metric name")
        mnames.add(x["name"])
        need(isinstance(x["unit"], str) and UNIT.match(x["unit"]),
             k + ".unit", f"bad unit {x['unit']!r}")
        need(x["better"] in ("lower", "higher"), k + ".better",
             "lower or higher")
        need(x["source"] in E2E_SOURCES, k + ".source",
             "an end-to-end metric takes host_clock or device_trace")
        b = x["bound"]
        need(isinstance(b, (int, float)) and not isinstance(b, bool) and
             0.01 <= b <= 0.1, k + ".bound", "from 0.01 to 0.1")
        e2e_cells[x["name"]] = cells_of(x, k)
    need("setup_s" in e2e_cells and e2e_cells["setup_s"] == wnames,
         "end_to_end", "setup_s must be there, in every cell")

    pl = m["per_layer"]
    need(isinstance(pl, list) and 1 <= len(pl) <= 128, "per_layer",
         "1 to 128 metrics")
    pl_cells = set()
    for x in pl:
        k = f"per_layer[{x.get('name')}]"
        keys(x, k, {"name", "unit", "better", "source", "layer", "moves"},
             {"workloads"})
        name(x["name"], k + ".name")
        need(x["name"] not in mnames, k, "duplicate metric name")
        mnames.add(x["name"])
        need(isinstance(x["unit"], str) and UNIT.match(x["unit"]),
             k + ".unit", f"bad unit {x['unit']!r}")
        need(x["better"] in ("lower", "higher"), k + ".better",
             "lower or higher")
        need(x["source"] in SOURCES, k + ".source", f"one of {SOURCES}")
        name(x["layer"], k + ".layer")
        need(x["layer"] in LAYERS, k + ".layer",
             f"not one of PERF.md's layers {LAYERS}")
        need(x["moves"] in e2e_cells, k + ".moves",
             "must name an end-to-end metric")
        mine = cells_of(x, k)
        pl_cells |= mine
        need(mine <= e2e_cells[x["moves"]], k + ".moves",
             f"{x['moves']} is not reported in "
             f"{sorted(mine - e2e_cells[x['moves']])}")
        if x["name"].endswith("_roofline") or "mfu" in x["name"]:
            need(x["unit"] == "%", k + ".unit", "a roofline or mfu is in %")
        f = os.path.join(root, "benchmark", "metrics", x["name"] + ".json")
        need(os.path.isfile(f), k, f"no reader file {f}")
        spec = read_json(f)
        for same in ("layer", "unit", "moves"):
            need(spec.get(same) == x[same], k,
                 f"metrics/{x['name']}.json disagrees on {same!r}")
        mod, _, fn = spec.get("reader", "").rpartition(".")
        need(mod and os.path.isfile(os.path.join(
            root, "benchmark", mod.replace(".", "/") + ".py")), k,
            f"reader {spec.get('reader')!r} names no module of benchmark/")
    for w in wnames:
        others = [n for n, cs in e2e_cells.items()
                  if n != "setup_s" and w in cs]
        need(others, f"workloads[{w}]",
             "reports no end-to-end metric besides setup_s")
        need(w in pl_cells, f"workloads[{w}]", "reports no per-layer metric")


def main() -> int:
    try:
        check()
    except Bad as e:
        print(f"check_manifest: {e}", file=sys.stderr)
        return 1
    print("check_manifest: BENCHMARK.json and the files it names keep to "
          "the contract")
    return 0


if __name__ == "__main__":
    sys.exit(main())
