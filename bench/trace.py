"""Reduction of a profiler trace to device busy and idle time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a list
of plain events ``(plane, line, name, start_ns, dur_ns)``; ``reduce``
works on such a list only, so a test can hand it a small recorded one.

* The window is the host span ``bench.window`` that the harness opened
  around the measured window.
* Device events are those of the planes named ``/device:TPU:<k>``.  Busy
  time is the union of their op intervals (line ``XLA Ops``) inside the
  window, averaged over the devices that ran anything.
* Program time: the ``XLA Modules`` line holds one event per program
  execution, named after the jitted function (``jit_<name>(<id>)``); the
  time of a program is the sum of its events inside the window.
* Op time: the ``XLA Ops`` events are HLO instructions, named by their
  text up to `` = ``; a ``while`` holds the ops of its body, so op times
  nest and do not add up to the busy time.
* Idle gaps: the stretches of the window in which no device op ran, each
  named after the innermost ``bench.*`` host span that covers its middle
  (``idle`` when none does).
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str) -> list:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        device = bool(DEVICE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith("bench."):
                    continue
                out.append((plane.name, line.name, name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def lines(events: list) -> dict:
    """Event count of every (plane, line) that holds any."""
    out: dict = {}
    for p, line, *_ in events:
        out[f"{p} | {line}"] = out.get(f"{p} | {line}", 0) + 1
    return out


def _union(intervals):
    total, merged = 0, []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    for a, b in merged:
        total += b - a
    return total, merged


def op_name(event_name: str) -> str:
    """``%while.70 = (f32[48]...) while(...)`` -> ``while.70``."""
    return event_name.split(" = ")[0].lstrip("%")


def program_name(event_name: str) -> str:
    """``jit_csr_bisect(12)`` -> ``csr_bisect``."""
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def reduce(events: list) -> dict:
    """Window, busy and idle seconds, per-program and per-op device time."""
    win = [(s, s + d) for p, l, n, s, d in events if n == WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = win[0]
    window_s = (w1 - w0) * 1e-9

    def clip(s, d):
        return max(s, w0), min(s + d, w1)

    planes = sorted({p for p, *_ in events if DEVICE.match(p)})
    busy_per, merged_all = [], []
    ops: dict = {}
    programs: dict = {}
    for plane in planes:
        ivs = []
        for p, line, name, s, d in events:
            if p != plane:
                continue
            a, b = clip(s, d)
            if b <= a:
                continue
            if line == OPS_LINE:
                ivs.append((a, b))
                op = op_name(name)
                ops[op] = ops.get(op, 0) + (b - a)
            elif line == MODULES_LINE:
                prog = program_name(name)
                programs[prog] = programs.get(prog, 0) + (b - a)
        if ivs:
            busy, merged = _union(ivs)
            busy_per.append(busy)
            merged_all.extend(merged)
    n_dev = max(1, len(busy_per))
    busy_s = sum(busy_per) * 1e-9 / n_dev
    # idle gaps: no op on any device
    gaps = []
    if merged_all:
        _, merged = _union(merged_all)
        t = w0
        for a, b in merged:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < w1:
            gaps.append((t, w1))
    else:
        gaps.append((w0, w1))
    # host spans of one thread nest; sweep them with the gaps' middles in
    # time order and keep a stack of the open ones
    marks = []
    for p, l, n, s, d in events:
        if n.startswith("bench.") and n != WINDOW:
            marks.append((s, 1, n[len("bench."):]))
            marks.append((s + d, 0, None))
    for k, (a, b) in enumerate(gaps):
        marks.append(((a + b) // 2, 2, k))
    marks.sort(key=lambda m: (m[0], m[1]))
    idle_by: dict = {}
    stack: list = []
    for _, kind, what in marks:
        if kind == 1:
            stack.append(what)
        elif kind == 0:
            stack.pop()
        else:
            a, b = gaps[what]
            name = stack[-1] if stack else "idle"
            idle_by[name] = idle_by.get(name, 0) + (b - a)
    top = lambda d: [[k, v * 1e-9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": len(busy_per),
        "program_s": {k: v * 1e-9 for k, v in programs.items()},
        "device_ops": top(ops),
        "idle_gaps": top(idle_by),
    }
