"""`python3 -m benchmark.fault_selftest`: whole runs of the harness on the
CPU rehearsal cells (gpt-tiny), sound and with a fault planted, to see
`correct` come out as it must.  About a minute; not part of tier-1.

Each case goes through `run.main` as the command does (the rehearsal cells
ask for the `cpu` platform, so the look for a chip passes here) and reads
the result's line:

  * sound: `rehearse-serve-saturated-tiny` is correct, every check held,
    `pool_left` among them;
  * a token altered where it is produced: the engine's device sampler
    (`serving.engine._sample_rows`) returns the LEAST likely token of every
    row; the window serves as many requests as before, and the reference's
    logits refuse them (`logit_deficit_max` over its limit);
  * the closed loop's pool run dry (`rehearse-serve-saturated-dry-tiny`,
    `max_rps` 1 for 300: 24 requests, gone on any host before the window
    is over): `pool_left` 0 is under `clients`, the run is not correct and
    the entry says which key to raise.  On a fast host nothing is left for
    the window and `completed` / `tokens_checked` fail beside it; that
    `pool_left` alone refuses a run needs no engine (`selftest` case (d)).
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from benchmark import run as bench_run  # noqa: E402
from benchmark.checks import held  # noqa: E402


def _run(cell: str, seed: int, seconds: float) -> dict:
    """One whole run of `run.main`; the result's line, parsed."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", "0"])
    assert rc == 0, (cell, rc)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _failing(out: dict) -> list:
    return sorted(k for k, c in out["checks"].items()
                  if not held({k: c}))


def test_sound_run():
    out = _run("rehearse-serve-saturated-tiny", 2 ** 31 + 33, 4)
    assert out["correct"] and _failing(out) == [], out
    assert out["checks"]["pool_left"]["value"] >= 8, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"           # the contract: it comes last


def test_token_altered_where_it_is_produced():
    import jax.numpy as jnp
    from paddle_tpu.serving import engine as engine_mod
    sound = engine_mod._sample_rows
    engine_mod._sample_rows = (
        lambda lg, *rest: jnp.argmin(lg, axis=-1).astype(
            jnp.argmax(lg, axis=-1).dtype))
    try:
        out = _run("rehearse-serve-saturated-tiny", 2 ** 31 + 34, 4)
    finally:
        engine_mod._sample_rows = sound
    assert not out["correct"], out
    assert _failing(out) == ["logit_deficit_max"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_pool_run_dry():
    out = _run("rehearse-serve-saturated-dry-tiny", 2 ** 31 + 35, 8)
    assert not out["correct"], out
    pool = out["checks"]["pool_left"]
    assert pool["value"] == 0 and pool["limit"] == 8, (
        "the host was too slow to drain 24 requests in 11 s?", pool)
    assert "max_rps" in pool["why"], pool
    assert set(_failing(out)) <= {"pool_left", "completed",
                                  "tokens_checked"}, out["checks"]
    assert out["failed"] == 0


def main() -> int:
    for name, fn in sorted(globals().items()):
        if name.startswith("test_"):
            fn()
            print(f"fault_selftest: {name} ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
