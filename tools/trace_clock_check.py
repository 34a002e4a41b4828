"""Do the host's and the device's planes of one profiler trace share a clock?

    python3 tools/trace_clock_check.py <trace_dir>

reads the newest `.xplane.pb` under `<trace_dir>/plugins/profile/`, and from
it the `engine.<phase>` events the serving engine's loop writes on the host
plane (serving/engine.py `_LoopPhases`) and the device plane's runs of
`jit_paged_decode_step`. It takes the two points where cause and effect
cross between the planes. A decode step cannot start on the device before
the `engine.decode_dispatch` that launches it starts on the host, and the
`engine.decode_sync` that fetches its tokens cannot end before the step
does. `launch_ms` (device start minus dispatch start) and `sync_ms` (sync
end minus device end) are each a one-way latency plus or minus the clocks'
offset: a negative one shows an offset outright, and two sessions of one
cell that differ in both, in opposite directions, show it too.

This is the gate ISSUE 26 set for putting the device's idle time down to the
host's phases (a median `sync_ms` under 0.5): it read 1.3-2.4 ms on a TPU
v5e, differently from session to session (PERF.md section 6, PR 26), so no
metric does that. Run it again on a trace of a later jax before trying.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import sys

PHASE_PREFIX = "engine."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
MODULES_LINE = "XLA Modules"
STEP_MODULE = re.compile(r"^jit_paged_decode_step(\(\d+\))?$")


def load(path: str) -> tuple[list, list]:
    """(`engine.*` events as [name, start_ns, end_ns, {stat: value}] by start,
    the decode step's runs on the first device plane as (start_ns, end_ns)).
    The phases' line is found by the events, not by a thread's name: the
    profiler names it after the process."""
    from jax.profiler import ProfileData

    phases, runs = [], []
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if on_device and line.name == MODULES_LINE and not runs:
                runs = sorted(
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events if STEP_MODULE.match(ev.name.strip())
                )
            elif not on_device:
                phases += [
                    [ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), dict(ev.stats)]
                    for ev in line.events if ev.name.startswith(PHASE_PREFIX)
                ]
    return sorted(phases, key=lambda ev: ev[1]), runs


def clock_check(phases: list, runs: list) -> dict:
    """`launch_ms` and `sync_ms` (median, least, most) over the decode steps
    whose dispatch, run and sync are all in the trace, and the share of them
    in which either is negative: the host first. A sync's run is the one,
    among those that overlap the time from its dispatch's start to its own
    end, that ends nearest that end. The median launch includes steps that
    queued behind a prefill chunk: the least is the one to read."""
    launch, sync, unpaired, launched = [], [], 0, None
    for name, start, end, _stats in phases:
        if name == PHASE_PREFIX + "decode_dispatch":
            launched = start
        elif name == PHASE_PREFIX + "decode_sync" and launched is not None:
            run = min((r for r in runs if r[0] < end and r[1] > launched), key=lambda r: abs(r[1] - end), default=None)
            if run is None:
                unpaired += 1
            else:
                launch.append((run[0] - launched) / 1e6)
                sync.append((end - run[1]) / 1e6)
            launched = None
    if not sync:
        return {"steps": 0, "unpaired": unpaired}

    def spread(xs: list) -> dict:
        return {"median": statistics.median(xs), "least": min(xs), "most": max(xs)}

    return {
        "steps": len(sync), "unpaired": unpaired, "launch_ms": spread(launch), "sync_ms": spread(sync),
        "host_first_share": sum(1 for a, b in zip(launch, sync) if a < 0 or b < 0) / len(sync),
    }


def main(argv: list) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reading a trace needs no chip
    paths = sorted(glob.glob(os.path.join(argv[0], "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    phases, runs = load(paths[-1])
    clock = clock_check(phases, runs)
    print(f"{len(phases)} engine.* events, {len(runs)} runs of jit_paged_decode_step, {clock['steps']} paired, {clock['unpaired']} unpaired")
    for key in ("launch_ms", "sync_ms"):
        if key in clock:
            print(f"{key}: median {clock[key]['median']:.3f} least {clock[key]['least']:.3f} most {clock[key]['most']:.3f}")
    if clock["steps"]:
        print(f"host first in {100 * clock['host_first_share']:.1f}% of steps")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
