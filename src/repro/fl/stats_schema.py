"""Schema pins for ``FLResult.driver_stats`` and ``BENCH_engine.json``.

Downstream consumers — the benchmark report, the CI smoke assertions, any
plotting against BENCH_engine.json — read these dicts by key.  A renamed or
silently-dropped key is invisible to the type checker and shows up as a KeyError
(or worse, a plot of nothing) long after the driver change that caused it.
This module is the one place the contract lives:

* :data:`DRIVER_STATS_SCHEMA` — required keys and types per driver-stats
  *leg*: the base scan keys every compiled job reports, plus the conditional
  ``paged`` and ``async`` groups a job opts into;
* :func:`validate_driver_stats` — checks an ``FLResult.driver_stats`` dict
  against the schema (the loop drivers report ``{}``, which is valid);
* :func:`validate_bench_report` — checks the BENCH_engine.json structure
  before it is written, so a malformed report never lands in the repo.

The schema is *sync-tested*: tests/test_stats_schema.py validates the stats
of real driver runs, so the pin and the driver cannot drift apart silently.
"""
from __future__ import annotations

import numbers
from typing import Any, Dict, Mapping, Tuple

# key -> accepted types.  bool is an int subclass in Python; the entries
# below that mean "a real number, not a flag" exclude bools explicitly in
# _check_type rather than via the type tuple.
_NUM = (numbers.Real,)
_INT = (numbers.Integral,)

DRIVER_STATS_SCHEMA: Dict[str, Dict[str, Tuple[type, ...]]] = {
    # every compiled scan job, any configuration
    "scan": {
        "driver": (str,),
        "pipeline": (bool,),
        "store": (str,),
        "chunks": _INT,
        "speculative_chunks": _INT,
        "cancelled_chunks": _INT,
        "setup_store_s": _NUM,
        "host_build_s": _NUM,
        "dispatch_s": _NUM,
        "device_wait_s": _NUM,
        "host_flush_s": _NUM,
        "compile_s": _NUM,
        "total_s": _NUM,
        "steps_useful": _INT,
        "steps_executed": _INT,
        "steps_bucket": _INT,
        "schedule_bytes_host": _INT,
        "page_bytes_h2d": _INT,
        "peak_live_bytes": _INT,
        "compiles_chunk": _INT,
        "compiles_total": _INT,
    },
    # models that count their experts' routed assignments (a dropless MoE)
    # report the mean over flushed rounds of each round's largest per-expert
    # count over the mean count (averaged over its MoE layers)
    "moe": {
        "expert_load_max_over_mean": _NUM,
    },
    # async_rounds=AsyncConfig(...) jobs additionally report the staleness leg
    "async": {
        "async_max_staleness": _INT,
        "async_arrivals": _INT,
        "async_pending_at_exit": _INT,
    },
}

# keys a consumer may attach after the run without invalidating the stats
# (the benchmark stamps its own compile count onto each leg's stats)
OPTIONAL_EXTRAS = frozenset({"bench_compiles"})


def _check_type(key: str, value: Any, types: Tuple[type, ...]) -> None:
    if bool not in types and isinstance(value, bool):
        raise ValueError(f"driver_stats[{key!r}] must be numeric, got bool")
    if not isinstance(value, types):
        raise ValueError(
            f"driver_stats[{key!r}] must be {'/'.join(t.__name__ for t in types)}, "
            f"got {type(value).__name__} ({value!r})"
        )


def validate_driver_stats(stats: Mapping[str, Any]) -> None:
    """Validate an ``FLResult.driver_stats`` dict against the schema.

    ``{}`` (the loop drivers) is valid.  A non-empty dict must carry every
    base scan key; presence of any ``async_*`` key requires the whole async
    leg.  Unknown keys are rejected — an unknown key is either a typo or a
    new stat that must be added to the schema (and thereby to the pin).
    """
    if not stats:
        return
    base = DRIVER_STATS_SCHEMA["scan"]
    asyn = DRIVER_STATS_SCHEMA["async"]
    for key, types in base.items():
        if key not in stats:
            raise ValueError(f"driver_stats missing required key {key!r}")
        _check_type(key, stats[key], types)
    has_async = any(k in stats for k in asyn)
    if has_async:
        for key, types in asyn.items():
            if key not in stats:
                raise ValueError(
                    f"driver_stats has async keys but is missing {key!r}"
                )
            _check_type(key, stats[key], types)
    moe = DRIVER_STATS_SCHEMA["moe"]
    for key, types in moe.items():
        if key in stats:
            _check_type(key, stats[key], types)
    known = set(base) | set(moe) | (set(asyn) if has_async else set()) | OPTIONAL_EXTRAS
    unknown = set(stats) - known
    if unknown:
        raise ValueError(
            f"driver_stats has unknown keys {sorted(unknown)}; add them to "
            "repro.fl.stats_schema.DRIVER_STATS_SCHEMA (the consumer contract) "
            "or fix the typo"
        )
    if stats.get("driver") != "scan":
        raise ValueError(
            f"driver_stats['driver'] must be 'scan', got {stats.get('driver')!r}"
        )
    if stats.get("store") not in ("resident", "paged"):
        raise ValueError(
            f"driver_stats['store'] must be 'resident' or 'paged', got "
            f"{stats.get('store')!r}"
        )


_REPORT_REQUIRED = {
    "benchmark": (str,),
    "devices": _INT,
    "backend": (str,),
    "mode": (str,),
    "engines": (dict,),
}


def validate_bench_report(report: Mapping[str, Any]) -> None:
    """Validate the BENCH_engine.json structure before it is written.

    Requires the top-level identity keys and, per engine leg, a positive
    ``s_per_round`` with its ``rounds_per_s`` reciprocal; a leg's optional
    ``compiles`` entry must be a dict of ints (``total``, and ``chunk`` for
    scan legs).
    """
    for key, types in _REPORT_REQUIRED.items():
        if key not in report:
            raise ValueError(f"bench report missing required key {key!r}")
        _check_type(key, report[key], types)
    if not report["engines"]:
        raise ValueError("bench report has no engine legs")
    for leg, entry in report["engines"].items():
        if not isinstance(entry, Mapping):
            raise ValueError(f"engine leg {leg!r} must be a dict")
        if "s_per_round" not in entry:
            raise ValueError(f"engine leg {leg!r} missing s_per_round")
        spr = entry["s_per_round"]
        if not isinstance(spr, numbers.Real) or isinstance(spr, bool) or spr <= 0:
            raise ValueError(
                f"engine leg {leg!r} s_per_round must be a positive number, "
                f"got {spr!r}"
            )
        rps = entry.get("rounds_per_s")
        if rps is not None and (
            not isinstance(rps, numbers.Real) or isinstance(rps, bool)
        ):
            raise ValueError(
                f"engine leg {leg!r} rounds_per_s must be numeric or None"
            )
        compiles = entry.get("compiles")
        if compiles is not None:
            if not isinstance(compiles, Mapping) or "total" not in compiles:
                raise ValueError(
                    f"engine leg {leg!r} compiles must be a dict with 'total'"
                )
            for ck, cv in compiles.items():
                if cv is not None and (
                    not isinstance(cv, numbers.Integral) or isinstance(cv, bool)
                ):
                    raise ValueError(
                        f"engine leg {leg!r} compiles[{ck!r}] must be an int"
                    )
