"""`python3 -m benchmark.span_selftest`: the span readers checked against
hand-worked numbers on a synthetic trace.  CPU only, seconds, no model; not
part of tier-1.

How the readers find a run's trace is in `span_readers`' docstring; here a
reader is pointed at a file through `obs["span_trace_path"]`.

Times in microseconds (offset_ps / 1e6); window = bench.window = [0, 200].

  device 0 ops: decode program   fusion.1 [10,30]  fusion.2 [35,60]
                                 (while.9 [10,60]: a container, ignored)
                prefill program  fusion.3 [110,130]
                decode program   fusion.1 [150,170]  fusion.2 [172,180]
  idle gaps:    [0,10] [30,35] [60,110] [130,150] [170,172] [180,200]
                = 10 + 5 + 50 + 20 + 2 + 20 = 107 us -> 53.5 %

  the scheduler thread, iteration 1 [2,98]:
    sweep [2,4]  admit [4,6]  decode.build [6,8]  decode.dispatch [8,14]
    decode.fetch [14,70]  decode.emit [70,96]
  iteration 2 [98,196]:
    sweep [98,99]  admit [99,100]  admit.wave [100,102]
    prefill.dispatch [102,112] (100 prompt tokens of 1024 computed)
    prefill.fetch [112,134]  prefill.emit [134,138]  decode.build [138,140]
    decode.dispatch [140,152]  decode.fetch [152,184]  decode.emit [184,196]
  iteration 3 [196,197]: sweep [196,197];  then serving.wait [197,200]
  another thread: prefill.dispatch [20,25] (300 of 512; the chip is busy),
  and one after the window, [205,210] (1000 of 1024), which must not count

  fetch, trailing only: the first fetch [14,70] holds a bubble inside the
    program ([30,35]) and the wait after it; the last op ending inside ends
    at 60, so [60,70] = 10 counts.  [112,134]: after 130 -> 4.  [152,184]:
    after 180 -> 4.  18 us -> 9 %.  Without `trailing_only`: 15 + 4 + 6 =
    25 us -> 12.5 %; the bubbles are the 7 us between (3.5 %).
  emit:    [70,96] = 26, [134,138] = 4, [184,196] = 12 -> 42 us -> 21 %
  prepare: 2 + 2 + 2 + [8,10] 2 = 8;  1 + 1 + 2 + [102,110] 8 = 12;
           2 + [140,150] 10 = 12;  sweep [196,197] 1 -> 33 us -> 16.5 %
  wait:    [197,200] -> 3 us -> 1.5 %
  rest:    107 - 18 - 7 - 42 - 33 - 3 = 4 us -> 2 %: [0,2] before the first
           iteration and [96,98] between its last leaf and its end (1 %)
  padding: 1 - (100 + 300) / (1024 + 512) = 73.958333... %
"""
from __future__ import annotations

import os
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from benchmark import span_readers  # noqa: E402
from benchmark.selftest import close  # noqa: E402

_NAMES = ["bench.window", "serving.iteration", "serving.sweep",
          "serving.admit", "serving.admit.wave", "serving.decode.build",
          "serving.decode.dispatch", "serving.decode.fetch",
          "serving.decode.emit", "serving.prefill.dispatch",
          "serving.prefill.fetch", "serving.prefill.emit", "serving.wait",
          "serving.decode", "serving.prefill"]
_STATS = ["rows", "batch_rows", "bucket", "prompt_tokens", "padded_tokens"]


def _event(name, start, end, **stats):
    s = "".join(f" stats {{ metadata_id: {_STATS.index(k) + 1} "
                f"int64_value: {v} }}" for k, v in stats.items())
    return (f"    events {{ metadata_id: {_NAMES.index(name) + 1} offset_ps: "
            f"{start * 10 ** 6} duration_ps: {(end - start) * 10 ** 6}{s} }}")


_SCHEDULER = [
    ("serving.iteration", 2, 98), ("serving.sweep", 2, 4),
    ("serving.admit", 4, 6), ("serving.decode", 6, 96),
    ("serving.decode.build", 6, 8), ("serving.decode.dispatch", 8, 14),
    ("serving.decode.fetch", 14, 70), ("serving.decode.emit", 70, 96),
    ("serving.iteration", 98, 196), ("serving.sweep", 98, 99),
    ("serving.admit", 99, 100), ("serving.admit.wave", 100, 102),
    ("serving.prefill", 102, 138),
    ("serving.prefill.dispatch", 102, 112,
     dict(rows=1, batch_rows=4, bucket=256, prompt_tokens=100,
          padded_tokens=1024)),
    ("serving.prefill.fetch", 112, 134), ("serving.prefill.emit", 134, 138),
    ("serving.decode", 138, 196), ("serving.decode.build", 138, 140),
    ("serving.decode.dispatch", 140, 152), ("serving.decode.fetch", 152, 184),
    ("serving.decode.emit", 184, 196),
    ("serving.iteration", 196, 197), ("serving.sweep", 196, 197),
    ("serving.wait", 197, 200)]
_OTHER = [
    ("serving.prefill.dispatch", 20, 25,
     dict(rows=3, batch_rows=4, bucket=128, prompt_tokens=300,
          padded_tokens=512)),
    ("serving.prefill.dispatch", 205, 210,
     dict(rows=4, batch_rows=4, bucket=256, prompt_tokens=1000,
          padded_tokens=1024))]


def _host_plane(with_serving: bool = True) -> str:
    def line(i, name, events):
        body = "\n".join(_event(e[0], e[1], e[2], **(e[3] if len(e) > 3
                                                     else {}))
                         for e in events)
        return (f'  lines {{ id: {i} name: "{name}" timestamp_ns: 0\n'
                f'{body}\n  }}\n')
    lines = line(1, "python3", [("bench.window", 0, 200)])
    if with_serving:
        lines += line(2, "engine-scheduler", _SCHEDULER)
        lines += line(3, "engine-scheduler-2", _OTHER)
    meta = "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(_NAMES))
    meta += "".join(f'  stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{n}" }} }}\n' for i, n in enumerate(_STATS))
    return f'planes {{ id: 2 name: "/host:CPU"\n{lines}{meta}}}\n'


_DEVICE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 50000000 }
    events { metadata_id: 2 offset_ps: 35000000 duration_ps: 25000000 }
    events { metadata_id: 3 offset_ps: 110000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 150000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 172000000 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.0), kind=kLoop, calls=%fc.1" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop, calls=%fc.2" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.2), kind=kLoop, calls=%fc.3" } }
  event_metadata { key: 4 value { id: 4 name: "%while.9 = (u32[], bf16[8,128]{1,0}) while((u32[], bf16[8,128]{1,0}) %tuple.1), condition=%c, body=%b" } }
}
'''


def _obs(tmp: str, name: str, text: str) -> dict:
    from jax.profiler import ProfileData
    path = os.path.join(tmp, name + ".xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return {"span_trace_path": path}


def _spec_args(metric: str) -> dict:
    import json
    with open(os.path.join(_HERE, "metrics", metric + ".json")) as f:
        return json.load(f)["args"]


def test_idle_split():
    with tempfile.TemporaryDirectory() as d:
        obs = _obs(d, "full", _DEVICE + _host_plane())
        for kind, want in (("fetch", 9.0), ("emit", 21.0),
                           ("prepare", 16.5)):
            for cell in ("steady", "saturated"):
                args = _spec_args(f"engine.idle_{kind}_share.{cell}")
                close(span_readers.idle_under(obs, **args), want,
                      what=f"idle under {kind}, {cell}")
        fetch = _spec_args("engine.idle_fetch_share.steady")["spans"]
        close(span_readers.idle_under(obs, fetch), 12.5,
              what="fetch with the bubbles inside the programs")
        # nested events are a union, not a sum: serving.decode is [6,96]
        # (idle [6,10] [30,35] [60,96]) and [138,196] ([138,150] [170,172]
        # [180,196]), its emit phases lie inside
        close(span_readers.idle_under(
            obs, ["serving.decode.emit", "serving.decode"]),
            100.0 * (4 + 5 + 36 + 12 + 2 + 16) / 200, what="parent and child")
        close(span_readers.idle_under(obs, ["serving.wait"]), 1.5)
        assert span_readers.idle_under(obs, ["serving.no_such"]) is None
        s = span_readers.split(obs["span_trace_path"])
        idle = s["idle_pct"]
        close(idle["total"], 53.5, what="idle")
        close(idle["in_program_bubbles"], 3.5, what="bubbles")
        close(idle["wait"], 1.5)
        close(idle["rest"], 2.0, rel=1e-6, what="rest")
        close(idle["rest_inside_iterations"], 1.0, rel=1e-6)
        close(sum(idle[k] for k in ("fetch", "emit", "prepare", "wait",
                                    "in_program_bubbles", "rest")),
              idle["total"], what="the parts add up to the idle share")
        close(s["median_ms"]["serving.decode.fetch"], (56 + 32) / 2 * 1e-3)
        assert s["count"]["serving.prefill.dispatch"] == 2, s["count"]


def test_padding_share():
    with tempfile.TemporaryDirectory() as d:
        obs = _obs(d, "full", _DEVICE + _host_plane())
        for cell in ("steady", "saturated"):
            args = _spec_args(f"engine.prefill_padding_share.{cell}")
            close(span_readers.stat_complement_pct(obs, **args),
                  100.0 * (1 - 400 / 1536), what="padding share")
        assert span_readers.stat_complement_pct(
            obs, "serving.decode.fetch", "prompt_tokens",
            "padded_tokens") is None, "events without the stats"


def test_nothing_to_read():
    """A program from before the spans, and a trace with no device plane
    (a CPU rehearsal): nothing raises, the metric is left out."""
    with tempfile.TemporaryDirectory() as d:
        old = _obs(d, "parent", _DEVICE + _host_plane(with_serving=False))
        args = _spec_args("engine.idle_emit_share.steady")
        assert span_readers.idle_under(old, **args) is None
        pad = _spec_args("engine.prefill_padding_share.steady")
        assert span_readers.stat_complement_pct(old, **pad) is None
        cpu = _obs(d, "cpu", _host_plane())
        assert span_readers.idle_under(cpu, **args) is None
        close(span_readers.stat_complement_pct(cpu, **pad),
              100.0 * (1 - 400 / 1536), what="counts need no device")
    assert span_readers.trace_path("no-such-cell") is None
    assert span_readers._workload(["run.py", "--workload", "a"]) == "a"
    assert span_readers._workload(["run.py", "--workload=b", "--x"]) == "b"
    assert span_readers._workload(["run.py"]) is None


def main() -> int:
    for name, fn in sorted(globals().items()):
        if name.startswith("test_"):
            fn()
            print(f"span_selftest: {name} ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
