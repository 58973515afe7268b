"""`python3 -m benchmark.selftest`: the yardstick checked against hand-worked
numbers.  CPU only, seconds, no model; not part of tier-1.

  * the trace reduction, on a synthetic trace whose answers are worked out
    below by hand and on a 12 ms cut of a trace recorded on a v5e
    (`testdata/v5e-train-step-boundary.xplane.pb`: the end of one 1.3B train
    step and the start of the next, one flash forward call inside), where an
    independent rasterized union must agree;
  * the FLOP and byte functions on hand-worked shapes;
  * the traffic generator: the same seed gives the same requests, another
    seed the same multiset of sizes in another order;
  * the closed loop's supply: every shipped closed-loop mix builds a pool
    at least twice what the newest measured rate consumes; a run whose pool
    ran dry is refused, one with a block left passes, an open loop is never
    judged; `order_seed` fixes the closed loop's order too; each serve
    driver's notes carry `pool_left`;
  * the manifest check accepts the committed manifest.
"""
from __future__ import annotations

import inspect
import json
import math
import os
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from benchmark import check_manifest, flops, readers, trace_reduce, traffic  # noqa: E402

# times in microseconds (offset_ps / 1e6); window = bench.window = [0, 100]
#   device 0 ops: fusion.1 [0,30]  while.2 [0,90] (container, ignored)
#                 flash fwd call [35,45] (3 operands)  bwd call [45,65] (6)
#                 all-reduce.7 [70,80]   sort.5 [90,95], again [95,100)
#   async line:   all-gather-start.3 [60,75]
#   busy   = 30 + 10 + 20 + 10 + 10 = 80 us -> idle 20 %
#   gaps   = [30,35] under bench.fetch, [65,70] under bench.client_wait but
#            inside host PjitFunction(decode) -> named by it, [80,90] nothing
#   collectives = union([70,80], [60,75]) = 20 us -> 20 %
_SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 35000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 45000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 70000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 90000000 duration_ps: 5000000 }
    events { metadata_id: 6 offset_ps: 95000000 duration_ps: 5000000 }
  }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 60000000 duration_ps: 15000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 8 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0:T(8,128)(2,1)} %p.0), kind=kLoop, calls=%fc.1" } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = (u32[]{:T(128)}, bf16[8,128]{1,0:T(8,128)(2,1)}) while((u32[]{:T(128)}, bf16[8,128]{1,0}) %tuple.1), condition=%c, body=%b" } }
  event_metadata { key: 3 value { id: 3 name: "%closed_call.9 = (bf16[4,256,64]{2,1,0:T(8,128)(2,1)}, f32[4,256,1]{2,1,0:T(8,128)}) custom-call(bf16[4,3,256,64]{3,2,1,0} %bitcast.1, bf16[4,3,256,64]{3,2,1,0} %bitcast.2, bf16[4,3,256,64]{3,2,1,0} %bitcast.3), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%checkpoint.10 = bf16[4,3,256,64]{3,2,1,0:T(8,128)(2,1)} custom-call(bf16[4,3,256,64]{3,2,1,0} %a, bf16[4,3,256,64]{3,2,1,0} %b, bf16[4,3,256,64]{3,2,1,0} %c, bf16[4,256,64]{2,1,0} %d, f32[4,256,1]{2,1,0} %pallas_call.26, f32[4,256,1]{2,1,0} %copy.83), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 5 value { id: 5 name: "%all-reduce.7 = f32[128]{0:T(128)} all-reduce(f32[128]{0:T(128)} %x), replica_groups={{0,1}}, to_apply=%add" } }
  event_metadata { key: 6 value { id: 6 name: "%sort.5 = (f32[25,50304]{1,0:T(8,128)S(1)}, s32[25,50304]{1,0:T(8,128)}) sort(f32[25,50304]{1,0:T(8,128)S(1)} %gte.400, s32[25,50304]{1,0:T(8,128)S(1)} %iota.70), dimensions={1}" } }
  event_metadata { key: 7 value { id: 7 name: "%all-gather-start.3 = (bf16[64]{0}, bf16[128]{0}) all-gather-start(bf16[64]{0} %y), dimensions={0}" } }
  event_metadata { key: 8 value { id: 8 name: "jit_step_fn(123)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 28000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 50000000 duration_ps: 30000000 }
  }
  lines { id: 2 name: "engine-scheduler" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 64000000 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fetch" } }
  event_metadata { key: 3 value { id: 3 name: "bench.client_wait" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(decode)" } }
}
'''


def close(a, b, rel=1e-9, what=""):
    assert abs(a - b) <= rel * max(abs(a), abs(b), 1e-30), (what, a, b)


def test_synthetic_trace():
    from jax.profiler import ProfileData
    blob = ProfileData.text_proto_to_serialized_xspace(_SYNTHETIC)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.xplane.pb")
        with open(path, "wb") as f:
            f.write(blob)
        red = trace_reduce.reduce_trace(path)
    us = 1e-6
    close(red["window_s"], 100 * us, what="window")
    close(red["busy_s"], 80 * us, what="busy")
    close(red["collective_s"], 20 * us, what="collectives")
    ops = red["devices"][0]["ops"]
    assert "while.2" not in ops and "jit_step_fn(123)" not in ops, ops
    close(ops["sort.5"], 10 * us, what="sort.5 twice")
    close(ops["checkpoint.10"], 20 * us)
    assert [(n, k) for n, k, _ in red["custom_calls"]] == [
        ("closed_call.9", 3), ("checkpoint.10", 6)], red["custom_calls"]
    gaps = red["gaps"]
    assert set(gaps) == {"bench.fetch", "host:PjitFunction(decode)",
                         "unattributed"}, gaps
    close(gaps["bench.fetch"], 5 * us)
    close(gaps["host:PjitFunction(decode)"], 5 * us)
    close(gaps["unattributed"], 10 * us)
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0][0] == "fusion.1"
    close(bd["device_ops"][0][1], 30 * us)
    assert all(len(n) <= 64 for n, _ in bd["device_ops"] + bd["idle_gaps"])
    obs = {"trace": red, "device_kind": "TPU v5 lite",
           "flash_shape": {"bh": 4, "t": 256, "d": 64}}
    close(readers.device_idle_share(obs), 20.0, what="idle share")
    close(readers.collective_share(obs), 20.0, what="collective share")
    # flash at this toy shape is memory-bound: causal fwd needs 33,554,432
    # FLOPs (0.17 us) but 4*(4*256*64)*2 + 4*256*4 = 528,384 B (0.645 us);
    # bwd 2.5x the FLOPs (0.43 us) and 1,056,768 B (1.29 us); over 30 us
    want = 100.0 * ((528384 + 1056768) / 819e9) / (30 * us)
    close(readers.flash_roofline(obs), want, what="flash roofline")


def test_recorded_trace():
    path = os.path.join(_HERE, "testdata",
                        "v5e-train-step-boundary.xplane.pb")
    red = trace_reduce.reduce_trace(path)
    close(red["window_s"], 0.012, rel=1e-6, what="recorded window")
    # independent union: rasterize device 0's ops onto a 10 ns grid
    from jax.profiler import ProfileData
    grid = bytearray(1_200_000)
    for p in ProfileData.from_file(path).planes:
        if p.name != "/device:TPU:0":
            continue
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if trace_reduce.opcode(ev.name) in ("while", "conditional",
                                                    "call"):
                    continue
                a, b = int(ev.start_ns // 10), int(-(-ev.end_ns // 10))
                grid[a:b] = b"\x01" * (b - a)
    close(red["busy_s"], sum(grid) * 1e-8, rel=2e-3, what="recorded busy")
    assert 0.0 < red["window_s"] - red["busy_s"] < 1e-4   # ~48 us of fetch
    assert max(red["gaps"], key=red["gaps"].get) == "bench.fetch", red["gaps"]
    assert [(n, k) for n, k, _ in red["custom_calls"]] == [
        ("closed_call.9", 3)], red["custom_calls"]
    close(red["custom_calls"][0][2], 399e-6, rel=5e-3, what="flash fwd time")
    assert all("=" not in n and len(n) <= 64
               for n in red["devices"][0]["ops"])


def test_flops():
    m = {"hidden_size": 2048, "num_layers": 24, "vocab_size": 50304,
         "max_position_embeddings": 2048, "intermediate_size": 8192}
    # per layer 12 h^2 + 13 h = 50,358,272; embeddings (50304 + 2048) * 2048
    n = 50304 * 2048 + 2048 * 2048 + 24 * (12 * 2048 ** 2 + 13 * 2048) + 4096
    assert n == 1_315_819_520 and flops.gpt_num_params(m) == n
    close(flops.gpt_train_flops_per_token(m, 1024),
          6 * n + 12 * 24 * 2048 * 1024)
    f, b = flops.flash_fwd_cost(128, 1024, 1024, 128, causal=True)
    assert f == 2 * 128 * 1024 * 1024 * 128           # 34,359,738,368
    assert b == 128 * 4 * 1024 * 128 * 2 + 128 * 1024 * 4
    f2, _ = flops.flash_bwd_cost(128, 1024, 1024, 128, causal=True)
    assert f2 == 2.5 * f
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.least_time_s(f, b, peak)
    assert bound == "compute"
    close(t, f / 197e12)
    assert flops.least_time_s(1.0, 1e9, peak)[1] == "memory"
    try:
        flops.peaks("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def test_traffic():
    lengths = {"prompt": {"median": 256, "sigma": 0.9, "min": 16,
                          "max": 1536},
               "output": {"median": 96, "sigma": 0.6, "min": 8, "max": 384}}
    mix = dict(lengths, loop="open", rate_rps=4.0, ramp_s=5)
    a = traffic.make_requests(mix, 3000000001, 40, 50304)
    b = traffic.make_requests(mix, 3000000001, 40, 50304)
    c = traffic.make_requests(mix, 17, 40, 50304)
    assert a == b, "the same seed must give the same requests"
    assert a != c

    def sizes(rs):
        return (sorted(len(r["prompt"]) for r in rs if r["counted"]),
                sorted(r["max_tokens"] for r in rs if r["counted"]))
    assert sizes(a) == sizes(c), "a seed may reorder the work, never change it"
    w = [r for r in a if r["counted"]]
    assert len(w) == 160 and sum(not r["counted"] for r in a) == 20
    assert all(0.0 <= r["due"] < 40.0 for r in w)
    assert all(-5.0 <= r["due"] < 0.0 for r in a if not r["counted"])
    assert all(16 <= len(r["prompt"]) <= 1536 and 8 <= r["max_tokens"] <= 384
               for r in a)
    assert sorted(len(r["prompt"]) for r in w)[80] in range(250, 263)
    # `order_seed`: every seed replays one schedule with other token ids
    def plan(rs):
        return [(r["due"], len(r["prompt"]), r["max_tokens"]) for r in rs]
    fixed = dict(mix, order_seed=3)
    f1 = traffic.make_requests(fixed, 1, 40, 50304)
    f2 = traffic.make_requests(fixed, 2, 40, 50304)
    assert plan(f1) == plan(f2) and plan(f1) != plan(a)
    assert f1[0]["prompt"] != f2[0]["prompt"] and sizes(f1) == sizes(a)
    closed = dict(lengths, loop="closed", clients=48, max_rps=12, ramp_s=10)
    e = traffic.make_requests(closed, 5, 40, 50304)
    assert len(e) % 48 == 0 and len(e) >= 48 + 12 * 50
    first, second = e[:48], e[48:96]
    assert sorted(len(r["prompt"]) for r in first) == \
        sorted(len(r["prompt"]) for r in second)
    assert traffic.prefill_buckets(a, 8, 2048) == [32, 64, 128, 256, 512,
                                                   1024, 2048]


_LEAD_S = 2.0                      # serve_driver._LEAD_S
_ROOT = os.path.dirname(_HERE)


def _newest_levels() -> dict:
    """traffic mix -> (the newest `serve_tokens_per_s` the driver's ledger
    holds for a cell of that mix, the higher of its two sides; which line),
    through the manifest's cells.  Nothing where the checkout has no ledger
    or the ledger no line: such a mix is not judged by case (a)."""
    path = os.path.join(_ROOT, "PERF_LEDGER.jsonl")
    if not os.path.exists(path):
        return {}
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        mix_of = {c["name"]: c["traffic"] for c in json.load(f)["workloads"]}
    out = {}
    with open(path) as f:
        for line in f:                         # oldest first: the last wins
            d = json.loads(line)
            sides = (d.get("end_to_end") or {}).get("serve_tokens_per_s")
            level = max((v for v in sides or [] if v), default=None)
            if level and d.get("workload") in mix_of:
                out[mix_of[d["workload"]]] = (
                    level, f"ledger, PR {d['pr']}, {d['workload']}")
    return out


def _closed_mixes():
    d = os.path.join(_HERE, "traffic")
    for f in sorted(os.listdir(d)):
        with open(os.path.join(d, f)) as fh:
            mix = json.load(fh)
        if mix.get("loop") == "closed":
            yield f[:-len(".json")], mix


def _report(requests, n_sent, seconds, done_until=None):
    """A client's report in which the first `n_sent` requests of the plan
    were sent and all but the last few answered in full."""
    out = []
    for i, r in enumerate(requests[:n_sent]):
        t = seconds * i / max(n_sent, 1)
        done = done_until is None or i < done_until
        out.append({"id": r["id"], "due": None, "sent": t, "status": 200,
                    "stamps": [t + 0.01] * r["max_tokens"] if done else [],
                    "done": done, "error": None})
    return out


def pool_outlasts(mix: dict, seconds: float, tokens_per_s: float):
    """Whether the pool `mix` builds holds at least twice the requests a
    system completing `tokens_per_s` sends in lead + ramp + window, its
    callers' requests in flight counted; with the two numbers."""
    pool = traffic.make_requests(mix, 7, seconds, 50304)
    mean_out = sum(r["max_tokens"] for r in pool) / len(pool)
    used = mix["clients"] + (tokens_per_s / mean_out) * (
        _LEAD_S + mix["ramp_s"] + seconds)
    return len(pool) >= 2 * used, len(pool), used


def test_closed_loop_pools():
    """(a) each shipped closed-loop mix, at the manifest's `run_seconds`,
    against the newest level the ledger holds for a cell of it (no table
    here: a mix the ledger does not know yet is built and not judged)."""
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    levels = _newest_levels()
    for name, mix in _closed_mixes():
        pool = traffic.make_requests(mix, 7, seconds, 50304)
        assert len(pool) % mix["clients"] == 0 and len(pool) == mix[
            "clients"] * (1 + math.ceil(mix["max_rps"] * (
                mix["ramp_s"] + seconds) / mix["clients"])), name
        if name in levels:
            ok, size, used = pool_outlasts(mix, seconds, levels[name][0])
            assert ok, (f"{name}: the pool of {size} is under twice the "
                        f"{used:.0f} requests that {levels[name]} sends: "
                        f"a benchmark PR has to raise its max_rps")
    # the rule itself, on the GPT mix: the level of the ledger's PR 32 lines
    # (a third over today's) passes, the old pool at today's level did not
    sat = dict(_closed_mixes())["chat-saturated"]
    assert pool_outlasts(sat, seconds, 2010.0)[0]
    assert pool_outlasts(sat, seconds, 2600.0)[0]
    assert not pool_outlasts(sat, seconds, 2700.0)[0]
    assert not pool_outlasts(dict(sat, max_rps=12), seconds, 1400.0)[0]


def test_closed_loop_supply():
    """(b) the rule the three serve drivers share."""
    mix = {"loop": "closed", "clients": 8, "max_rps": 2, "ramp_s": 1,
           "prompt": {"median": 40, "sigma": 0.9, "min": 4, "max": 180},
           "output": {"median": 12, "sigma": 0.6, "min": 2, "max": 40}}
    pool = traffic.make_requests(mix, 3, 3.0, 1000)
    assert len(pool) == 16                     # 1 + ceil(2 x 4 / 8) blocks
    # dry: every request of the plan was sent
    notes, why = traffic.closed_loop_supply(mix, pool, _report(pool, 16, 3.0),
                                            3.0)
    assert notes == {"pool_size": 16, "pool_left": 0, "in_flight_end": 0}
    assert why and "max_rps" in why and "not correct" in why, why
    # about to run dry: fewer left than callers who would each draw once more
    notes, why = traffic.closed_loop_supply(mix, pool, _report(pool, 9, 3.0),
                                            3.0)
    assert notes["pool_left"] == 7 and why
    # one block left: every caller could have drawn again
    notes, why = traffic.closed_loop_supply(
        mix, pool, _report(pool, 8, 3.0, done_until=3), 3.0)
    assert why is None and notes["pool_left"] == 8
    assert notes["in_flight_end"] == 5         # sent, not answered by the end
    # an open loop sends its whole schedule and is never judged
    opn = dict(mix, loop="open", rate_rps=4.0)
    plan = traffic.make_requests(opn, 3, 3.0, 1000)
    notes, why = traffic.closed_loop_supply(
        opn, plan, _report(plan, len(plan), 3.0), 3.0)
    assert why is None and notes["pool_left"] is None
    assert notes["pool_size"] == len(plan) == 16
    # a request the client never reached has no `sent`
    rep = _report(pool, 8, 3.0) + [dict(_report(pool, 9, 3.0)[8], sent=None)]
    assert traffic.closed_loop_supply(mix, pool, rep, 3.0)[0]["pool_left"] == 8


def test_closed_loop_order():
    """(c) `order_seed`: every seed draws one order of lengths from the
    pool, with other token ids; without it the seed draws the order."""
    _, sat = next(m for m in _closed_mixes() if m[0] == "chat-saturated")

    def plan(rs):
        return [(len(r["prompt"]), r["max_tokens"]) for r in rs]
    a = traffic.make_requests(sat, 1, 50, 50304)
    b = traffic.make_requests(sat, 2 ** 31 + 12345, 50, 50304)
    assert plan(a) == plan(b) and [r["id"] for r in a] == [r["id"] for r in b]
    assert a[0]["prompt"] != b[0]["prompt"]
    assert plan(a[:48]) != plan(a[48:96])      # blocks differ in order ...
    assert sorted(plan(a[:48])) != plan(a[:48])
    assert sorted(p for p, _ in plan(a[:48])) == \
        sorted(p for p, _ in plan(a[48:96]))   # ... never in their lengths
    loose = {k: v for k, v in sat.items() if k != "order_seed"}
    c = traffic.make_requests(loose, 1, 50, 50304)
    d = traffic.make_requests(loose, 2, 50, 50304)
    assert plan(c) != plan(d) != plan(a)
    for i in (0, 1):                           # the same work in every order
        assert sorted(x[i] for x in plan(c)) == \
            sorted(x[i] for x in plan(d)) == sorted(x[i] for x in plan(a))


def test_drivers_carry_the_supply():
    """(d) the three serve `run`s call the one helper, put its notes
    (`pool_left`, `pool_size`, `in_flight_end`) into theirs, make their
    `correct` of `held(checks)` alone and return the pool's entry beside it
    (`supply`), where `run.py` holds the result to it."""
    from benchmark import run as bench_run
    from benchmark import (serve_decoder_driver, serve_driver,
                           serve_latent_driver)
    from benchmark.checks import check, held
    for mod in (serve_driver, serve_decoder_driver, serve_latent_driver):
        src = inspect.getsource(mod.run)
        assert "supply, dry = traffic.closed_loop_supply(" in src, mod
        assert "**supply" in src, mod
        assert "correct=held(checks), checks=checks," in src, mod
        assert "supply=supply_check(mix, supply, dry)," in src, mod
    src = inspect.getsource(traffic.closed_loop_supply)
    assert all(k in src for k in ("pool_left", "pool_size", "in_flight_end"))
    src = inspect.getsource(bench_run.main)
    assert 'bool(res["correct"]) and held(supply)' in src
    mix = {"loop": "closed", "clients": 8}
    ok = serve_driver.supply_check(mix, {"pool_left": 8}, None)
    assert ok == {"pool_left": {"value": 8, "limit": 8, "holds": ">="}}
    assert held(ok)
    for dry in ({"pool_left": 7}, {"pool_left": 0}):
        bad = serve_driver.supply_check(mix, dry, "raise `max_rps`")
        assert not held(bad) and "max_rps" in bad["pool_left"]["why"]
    assert serve_driver.supply_check({"loop": "open"}, {"pool_left": None},
                                     None) == {}
    sound = serve_driver.serve_checks(
        5, 40, 0, 0, {"logit_deficit_max": (0.04, 0.1)},
        {"longest_context_checked": (70, 65)})
    assert held(sound) and "pool_left" not in sound
    assert sorted(sound) == ["compiles_in_window", "completed", "failed",
                             "logit_deficit_max", "longest_context_checked",
                             "tokens_checked"]
    for k, v in (("failed", 1), ("compiles_in_window", 2), ("completed", 0),
                 ("tokens_checked", 0), ("logit_deficit_max", 0.11),
                 ("logit_deficit_max", None),
                 ("logit_deficit_max", float("nan")),
                 ("longest_context_checked", 64)):
        assert not held({**sound, k: dict(sound[k], value=v)}), (k, v)
    assert not held({"x": check(1.0, None)}) and held({})


def test_manifest():
    check_manifest.check()
    assert check_manifest.NAME.match("train-step")
    assert not check_manifest.NAME.match("train step")
    assert not check_manifest.NAME.match("-x")
    assert check_manifest.UNIT.match("tokens/s/chip")
    assert not check_manifest.UNIT.match("tokens per second")
    assert check_manifest.is_width("kv_lora_rank")
    assert not check_manifest.is_width("num_layers")


def main() -> int:
    for name, fn in sorted(globals().items()):
        if name.startswith("test_"):
            fn()
            print(f"selftest: {name} ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
