"""The numbers a run's `correct` compares, each beside its limit.

A driver builds `checks` (name -> `check(...)`) and its `correct` IS
`held(checks)`: there is one comparison, here.  `run.py` prints the same
entries as the last lines of stderr and as the last key of the result.
"""
from __future__ import annotations


def check(value, limit, holds: str = "<=", why: str | None = None) -> dict:
    """One number compared: `value` `holds` (`<=` or `>=`) `limit`; `why`
    is said beside it when it does not hold."""
    out = {"value": value, "limit": limit, "holds": holds}
    if why:
        out["why"] = why
    return out


def held(checks: dict) -> bool:
    """Every number keeps to its limit; one that is missing (None) or not a
    number (NaN) does not."""
    return all(c["value"] is not None and c["limit"] is not None and
               (c["value"] <= c["limit"] if c["holds"] == "<=" else
                c["value"] >= c["limit"]) for c in checks.values())
