"""From a ``jax.profiler`` trace to four numbers and a breakdown.

``load_events`` flattens the ``.xplane.pb`` into plain rows
``[plane, line, name, start_ns, duration_ns]``; ``reduce_events`` works on
those rows alone, so a trimmed recording kept as JSON checks it on the CPU.

- busy: the union of the intervals in which an operation ran on a device
  (the device planes' ``XLA Ops`` line), averaged over the device planes;
- window: first device-op start to last device-op end over all devices;
- per-op seconds: SELF time on that line (an enclosing ``while`` or call
  does not count what its body's operations already count);
- idle gaps: the longest stretches with no device operation on the first
  device, each named by the host span that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_events(trace_dir: str) -> list[list]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(sorted(paths)[-1])
    rows = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name,
                             int(ev.start_ns), int(ev.duration_ns)])
    return rows


def short_name(name: str) -> str:
    """The trace prints a device operation as its whole HLO instruction;
    keep the instruction's name and its result's type and shape."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    return (lhs + " " + rhs.split("{")[0])[:120]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _self_times(events: list[tuple[int, int, str]]) -> dict[str, int]:
    """Self nanoseconds by name of properly nested (start, end, name)
    events."""
    out: dict[str, int] = defaultdict(int)
    stack: list[list] = []          # [end, name, child_ns, start]

    def close(entry):
        end, name, child, start = entry
        out[name] += max(0, (end - start) - child)
        if stack:
            stack[-1][2] += end - start

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        stack.append([end, name, 0, start])
    while stack:
        close(stack.pop())
    return out


class Summary:
    def __init__(self, busy_s, window_s, op_seconds, op_counts,
                 module_seconds, module_counts, idle_gaps, devices):
        self.busy_s, self.window_s = busy_s, window_s
        self.op_seconds = op_seconds          # {op name: self seconds}
        self.op_counts = op_counts            # {op name: events per device}
        self.module_seconds = module_seconds  # {program name: seconds}
        self.module_counts = module_counts    # {program name: runs}
        self.idle_gaps = idle_gaps            # [[host span, seconds], ...]
        self.devices = devices

    def matching(self, patterns: list[str]) -> tuple[float, float]:
        """(self seconds, events) per device of the device ops whose name
        matches any of the regular expressions."""
        regs = [re.compile(p) for p in patterns]
        names = [n for n in self.op_seconds
                 if any(r.search(n) for r in regs)]
        return (sum(self.op_seconds[n] for n in names),
                sum(self.op_counts[n] for n in names))

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[short_name(n), s] for n, s in ops],
                "idle_gaps": self.idle_gaps[:10]}


def reduce_events(rows: list[list]) -> Summary:
    by_plane: dict[str, list] = defaultdict(list)
    modules: dict[str, float] = defaultdict(float)
    module_runs: dict[str, int] = defaultdict(int)
    host: list[tuple[int, int, str]] = []
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            if line == OPS_LINE:
                by_plane[plane].append((start, start + dur, name))
            elif line == MODULES_LINE:
                program = re.sub(r"\(\d+\)$", "", name)
                modules[program] += dur / 1e9
                module_runs[program] += 1
        else:
            host.append((start, start + dur, name))
    if not by_plane:
        raise ValueError("the trace holds no device operation")
    lo = min(e[0] for evs in by_plane.values() for e in evs)
    hi = max(e[1] for evs in by_plane.values() for e in evs)
    busy, op_seconds = [], defaultdict(float)
    op_counts: dict[str, float] = defaultdict(float)
    for evs in by_plane.values():
        merged = _union([(a, b) for a, b, _ in evs])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, ns in _self_times(evs).items():
            op_seconds[name] += ns / 1e9 / len(by_plane)
        for _, _, name in evs:
            op_counts[name] += 1.0 / len(by_plane)
    first = sorted(by_plane)[0]
    merged = _union([(a, b) for a, b, _ in by_plane[first]])
    gaps = sorted(((b2 - e1, e1, b2) for (_, e1), (b2, _)
                   in zip(merged, merged[1:])), reverse=True)[:10]
    idle = []
    for length, g0, g1 in gaps:
        best, best_ns = "no host span", 0
        for h0, h1, name in host:
            over = min(h1, g1) - max(h0, g0)
            if over > best_ns:
                best, best_ns = name, over
        idle.append([best, length / 1e9])
    return Summary(sum(busy) / len(busy), (hi - lo) / 1e9, dict(op_seconds),
                   dict(op_counts), dict(modules), dict(module_runs), idle,
                   len(by_plane))


class Profile:
    """A traced stretch: ``start`` / ``stop`` around it, ``reduce`` once the
    window has closed. The trace directory is fixed, inside the run's
    working directory, and removed once read."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # host TraceMe spans only
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self) -> Summary:
        rows = load_events(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduce_events(rows)
