"""Builds the result line and validates it before it is printed.

A malformed line is refused by the driver after the chip time is spent, so
``build`` raises instead: the keys the contract names, every metric the
cell lists for this kind of run with a finite value and its unit, no share
of a peak or a roofline over 100%, and in a traced run
``0 < busy_s <= window_s``.
"""
from __future__ import annotations

import json
import math

DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class MalformedLine(ValueError):
    pass


def _share(name, unit):
    return unit == "%" and ("roofline" in name or "mfu" in name
                            or "share" in name)


def build(*, correct, attempted, failed, values, wanted, units, device,
          traced, breakdown=None, compared=None, allow_missing=False):
    """``values``: metric name -> number (None or absent: nothing read).
    ``wanted``: the metrics this run of the cell has to report.
    ``units``: metric name -> unit. Returns the JSON text."""
    metrics = {}
    for name in wanted:
        v = values.get(name)
        if v is None and allow_missing:  # a CPU rehearsal has no kernels
            continue
        if v is None:
            raise MalformedLine(f"metric {name!r} of this cell was not read")
        v = float(v)
        if not math.isfinite(v):
            raise MalformedLine(f"metric {name!r} is not finite: {v}")
        if _share(name, units[name]) and not 0.0 <= v <= 100.0 + 1e-9:
            raise MalformedLine(f"share {name!r} outside 0..100%: {v}")
        metrics[name] = {"value": v, "unit": units[name]}
    if "setup_s" not in metrics and not traced:
        raise MalformedLine("setup_s missing")
    for k in DEVICE_KEYS:
        if device.get(k) in (None, ""):
            raise MalformedLine(f"device.{k} missing")
    if not (isinstance(device["memory_peak_bytes"], int)
            and device["memory_peak_bytes"] > 0):
        raise MalformedLine("device.memory_peak_bytes must be a positive "
                            f"whole number: {device['memory_peak_bytes']!r}")
    if traced:
        w, b = device.get("window_s"), device.get("busy_s")
        if not (isinstance(w, float) and isinstance(b, float)
                and 0.0 < b <= w):
            raise MalformedLine(
                f"a traced run needs 0 < busy_s <= window_s: busy_s={b!r} "
                f"window_s={w!r}")
    if not (isinstance(attempted, int) and isinstance(failed, int)
            and attempted > 0 and 0 <= failed <= attempted):
        raise MalformedLine(f"attempted={attempted!r} failed={failed!r}")
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if traced and breakdown:
        for k in ("device_ops", "idle_gaps"):
            if len(breakdown.get(k, [])) > 10:
                raise MalformedLine(f"breakdown.{k} has over 10 entries")
        line["breakdown"] = breakdown
    line["compared"] = compared or {}
    if not line["compared"]:
        raise MalformedLine("nothing was compared: correct has no basis")
    return json.dumps(line)
