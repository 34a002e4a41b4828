"""From a profiler trace to numbers: the benchmark's own reduction.

Two steps, so that the second can be checked on a small recorded fixture
without jax or a chip:

    table = load_xplane(path)      # needs jax.profiler.ProfileData
    summary = reduce_table(table, clock_window_s)  # plain python

A table is {"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, dur_ns], ...]}]}]}. Device planes are named "/device:TPU:<n>";
their "XLA Ops" line holds one event for each operation the device ran
(named by its HLO text; a `while` spans the operations of its body), and
"XLA Modules" one for each run of a compiled program (`jit_<fn>(<id>)`).

The traced window. The device is recorded from somewhere inside
`start_trace` to somewhere inside `stop_trace`, so the profile is never
shorter than the container's clock between the first returning and the
second being called, and where the loop's threads hold the interpreter it
is longer by tens of milliseconds at both ends. The profile's own stamps
(plane "Task Environment", `profile_start_time` / `profile_stop_time`, the
base of every event's `start_ns`) do not bound the recording: the stop
stamp comes a quarter of a second after the last recorded operation of a
device that never idles (one real trace read on the chip, PR 36). So the
window is what the two lower bounds of the recording give:

    window_s = max(clock_window_s, span_s)

`span_s` being the first operation's start to the last one's end over all
device planes. `busy_s <= span_s <= window_s` then holds for ANY trace. A
device idle at both edges keeps the clock's window; a device that never
idles is measured over its own span, which understates its idle share by
at most what the recording reaches past both (a gap at either edge).
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# a Pallas (Mosaic) kernel is a custom call with this target in its HLO text
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, keep_lines: tuple = (OPS_LINE, MODULES_LINE, "Steps")) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in keep_lines:
                continue
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def module_base(name: str) -> str:
    """`jit_paged_decode_step(1234567)` -> `jit_paged_decode_step`."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_short(name: str) -> str:
    """An operation's event is named by its whole HLO text
    (`%fusion.167 = (f32[32,128]...) fusion(...)`): keep `fusion.167`."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def module_owner(modules: list):
    """XLA numbers operations anew in every program, so an operation is
    named `<module>/<operation>` by the module run it started in."""
    import bisect

    runs = sorted((s, s + d, module_base(n)) for n, s, d in modules)
    starts = [r[0] for r in runs]

    def owner(start: int) -> str:
        i = bisect.bisect_right(starts, start) - 1
        return runs[i][2] + "/" if i >= 0 and start < runs[i][1] else ""

    return owner


def self_times(events: list, owner) -> dict:
    """Seconds by operation with the time of the operations nested inside it
    taken out (a `while` spans its body's operations on the same line)."""
    out: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(0, own) / 1e9

    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([owner(start) + op_short(name), start + dur, dur])
    close(1 << 62)
    return out


def _union(intervals: list) -> tuple:
    """(covered length, merged intervals) of [start, end) pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def reduce_table(table: dict, clock_window_s: float = 0.0, top: int = 10) -> dict:
    """busy seconds (mean over the device planes), the traced window (the
    module's head says how it is made), per-module and per-op durations,
    and the idle gaps labelled by the modules on either side."""
    planes = [p for p in table["planes"] if DEVICE_PLANE.match(p["name"])]
    if not planes:
        raise ValueError("the trace has no device plane: nothing ran on a TPU while it was taken")
    busy, first, last = [], [], []
    modules: dict = {}
    ops: dict = {}
    gaps: dict = {}
    kernels: dict = {}
    for plane in planes:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        op_events = lines.get(OPS_LINE) or []
        mod_events = lines.get(MODULES_LINE) or []
        source = op_events or mod_events
        if not source:
            continue
        covered, _merged = _union([(s, s + d) for _n, s, d in source])
        busy.append(covered / 1e9)
        first.append(min(s for _n, s, _d in source))
        last.append(max(s + d for _n, s, d in source))
        for name, _start, dur in mod_events:
            modules.setdefault(module_base(name), []).append(dur / 1e6)
        owner = module_owner(mod_events)
        for name, seconds in self_times(op_events, owner).items():
            ops[name] = ops.get(name, 0.0) + seconds
        for name, start, dur in op_events:
            if KERNEL_TARGET in name:
                kernels.setdefault(owner(start) + op_short(name), []).append(dur / 1e6)
        ordered = sorted(mod_events or op_events, key=lambda ev: ev[1])
        for (n1, s1, d1), (n2, s2, _d2) in zip(ordered, ordered[1:]):
            gap = s2 - (s1 + d1)
            if gap > 0:
                label = f"after {module_base(n1)} before {module_base(n2)} (host: unattributed)"
                agg = gaps.setdefault(label, [0.0, 0.0, 0])
                agg[0] += gap / 1e9
                agg[1] = max(agg[1], gap / 1e9)
                agg[2] += 1
    if not busy:
        raise ValueError("the trace's device planes hold no operation")
    span_s = (max(last) - min(first)) / 1e9
    return {
        "busy_s": sum(busy) / len(busy),
        "span_s": span_s,
        "clock_window_s": clock_window_s,
        "window_s": max(clock_window_s, span_s),
        "device_planes": len(busy),
        "modules": {
            name: {"count": len(ms), "median_ms": statistics.median(ms), "total_s": sum(ms) / 1e3}
            for name, ms in modules.items()
        },
        "device_ops": [[n, s] for n, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v[0]] for n, v in sorted(gaps.items(), key=lambda kv: -kv[1][0])[:top]],
        "kernels": {
            name: {"count": len(ms), "median_ms": statistics.median(ms), "total_s": sum(ms) / 1e3}
            for name, ms in kernels.items()
        },
    }


def main(argv: list) -> int:
    """`python trace_reduce.py <trace_dir> <out.json> <clock_window_s>
    [--table out]`: run by the harness in a child process (this one may
    import jax; it is pinned to the CPU and never touches the chip)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    trace_dir, out_path, clock_window_s = argv[0], argv[1], float(argv[2])
    path = find_xplane(trace_dir)
    table = load_xplane(path)
    summary = reduce_table(table, clock_window_s)
    if "--table" in argv:
        with open(argv[argv.index("--table") + 1], "w") as f:
            json.dump(table, f)
    with open(out_path, "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
