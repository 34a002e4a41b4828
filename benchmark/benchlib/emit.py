"""The one place that builds a run's last line, and refuses a malformed one.

`emit()` RAISES instead of printing when the line is one the driver's check
would refuse (ledger, PR 22): a key missing, a metric of the cell without a
finite number, `busy_s` outside (0, window_s] in a traced run, a share of a
peak over 100%. The harness turns the exception into a non-zero exit with no
last line.
"""

from __future__ import annotations

import json
import math
import sys

SHARE_OF_PEAK = ("_roofline", "mfu", "_hbm_pct")


class MalformedLine(ValueError):
    pass


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def build_line(
    *,
    correct: bool,
    attempted: int,
    failed: int,
    metrics: dict,
    required: dict,
    device: dict,
    traced: bool,
    compared: dict,
    breakdown: dict | None = None,
    extra: dict | None = None,
) -> dict:
    """`metrics`: name -> value measured. `required`: name -> unit of every
    metric the cell must report in this run (end-to-end metrics when not
    traced, per-layer metrics when traced). Returns the line as a dict whose
    key order is the order printed (`compared` last)."""
    if not isinstance(correct, bool):
        raise MalformedLine("'correct' is not a boolean")
    for key, value in (("attempted", attempted), ("failed", failed)):
        if not isinstance(value, int) or isinstance(value, bool) or value < 0:
            raise MalformedLine(f"'{key}' is not a whole number >= 0: {value!r}")
    if attempted < 1:
        raise MalformedLine("nothing was attempted")
    if failed > attempted:
        raise MalformedLine(f"failed ({failed}) > attempted ({attempted})")
    out_metrics = {}
    for name, unit in required.items():
        if name not in metrics or metrics[name] is None:
            raise MalformedLine(f"metric {name!r} of this cell has no number")
        value = metrics[name]
        if not _finite(value):
            raise MalformedLine(f"metric {name!r} is not a finite number: {value!r}")
        if unit == "%" and any(tag in name for tag in SHARE_OF_PEAK) and not 0.0 < value <= 100.0:
            raise MalformedLine(f"{name} = {value}% is a share of a peak outside (0, 100]: the operations or bytes are counted too high, or the time leaves out part of the work")
        out_metrics[name] = {"value": value, "unit": unit}
    dev = {}
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if device.get(key) in (None, ""):
            raise MalformedLine(f"device.{key} is missing")
        dev[key] = device[key]
    if not isinstance(dev["count"], int) or dev["count"] < 1:
        raise MalformedLine(f"device.count = {dev['count']!r}")
    if not _finite(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] <= 0:
        raise MalformedLine(f"device.memory_peak_bytes = {dev['memory_peak_bytes']!r}")
    if traced:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not _finite(busy) or not _finite(window):
            raise MalformedLine(f"a traced run needs device.busy_s and device.window_s, got {busy!r}, {window!r}")
        if not 0.0 < busy <= window:
            raise MalformedLine(f"device.busy_s = {busy} is not in (0, window_s = {window}]")
        dev["busy_s"], dev["window_s"] = busy, window
    for name, pair in compared.items():
        if set(pair) != {"value", "limit"}:
            raise MalformedLine(f"compared.{name} needs exactly a value and a limit")
    line = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": out_metrics, "device": dev}
    if traced and breakdown:
        line["breakdown"] = {
            "device_ops": [[str(n), float(s)] for n, s in breakdown.get("device_ops", [])[:10]],
            "idle_gaps": [[str(n), float(s)] for n, s in breakdown.get("idle_gaps", [])[:10]],
        }
    for key, value in (extra or {}).items():
        if key not in line and key != "compared":
            line[key] = value
    line["compared"] = compared
    return line


def emit(line: dict, out=None, err=None) -> None:
    """Print the numbers compared as the last lines of standard error, then
    the line as the last line of standard output."""
    out = out or sys.stdout
    err = err or sys.stderr
    text = json.dumps(line, allow_nan=False)
    if "\n" in text:
        raise MalformedLine("the line would span more than one line")
    for name, pair in line["compared"].items():
        err.write(f"compared {name}: value {pair['value']} limit {pair['limit']}\n")
    err.write(f"correct: {line['correct']}\n")
    err.flush()
    out.write(text + "\n")
    out.flush()
