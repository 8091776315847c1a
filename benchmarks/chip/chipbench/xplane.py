"""Trace reduction: a profiler ``.xplane.pb`` to device busy time, per-op
and per-program device time, and idle gaps labelled by the host span open
during each.

Two stages, so that the second can be checked on a small recorded trace:

* :func:`load` reads the ``.xplane.pb`` of a traced window into a compact
  dict of events (nanoseconds on the profiler's clock):
  ``{"window": [t0, t1], "spans": [[name, start, dur], ...],
  "devices": [{"name", "ops": [[name, start, dur, kind, program]],
  "programs": [[name, start, dur], ...]}]}``;
* :func:`summarize` turns that dict into a :class:`Summary`.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip, their ``XLA Modules`` line one event
per program run.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, named ``bench:<what>``; the one
named ``bench:window`` bounds the traced window.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, PROGRAMS_LINE = "XLA Ops", "XLA Modules"
TOP_N = 10


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def _op_record(ev, programs) -> list:
    """[name, start, dur, kind, program] of one ``XLA Ops`` event, whose
    name is the op's HLO text: ``%<name> = <shape> <opcode>(...), ...``.
    ``kind`` is the custom-call target where there is one, else the
    opcode; ``program`` the ``XLA Modules`` event the op starts in."""
    head, _, rest = ev.name.partition(" = ")
    target = _TARGET.search(rest)
    opcode = _OPCODE.search(" " + rest.split(" ", 1)[-1]) if rest else None
    kind = target.group(1) if target else (opcode.group(1) if opcode else "")
    return [head.lstrip("%"), ev.start_ns, ev.duration_ns, kind,
            _program_at(programs, ev.start_ns)]


def _program_at(programs, t) -> str:
    i = bisect.bisect_right(programs, [t, float("inf")]) - 1
    if i >= 0 and programs[i][0] <= t <= programs[i][0] + programs[i][1]:
        return programs[i][2]
    return ""


def load(trace_dir) -> dict:
    """The compact event dict of the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    spans, devices = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            progs = sorted([ev.start_ns, ev.duration_ns, ev.name]
                           for ev in lines[PROGRAMS_LINE].events) \
                if PROGRAMS_LINE in lines else []
            ops = [_op_record(ev, progs) for ev in lines[OPS_LINE].events] \
                if OPS_LINE in lines else []
            devices.append((int(m.group(1)), {
                "name": plane.name, "ops": ops,
                "programs": [[n, s, d] for s, d, n in progs]}))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, ev.start_ns, ev.duration_ns])
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w = max(windows, key=lambda s: s[2])
    return {"window": [w[1], w[1] + w[2]],
            "spans": [s for s in spans if s[0] != WINDOW_SPAN],
            "devices": [d for _, d in sorted(devices)]}


def _clip(start: float, dur: float, t0: float, t1: float
          ) -> Optional[Tuple[float, float]]:
    s, e = max(start, t0), min(start + dur, t1)
    return (s, e) if e > s else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Device:
    name: str
    busy_ns: float
    busy: List[Tuple[float, float]]         # union of op intervals
    ops: List[list]                     # clipped [name, s, dur, kind, prog]
    programs: List[list]                    # clipped [name, s, dur]


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]
    devices: List[Device]
    spans: List[list]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(d.busy_ns for d in self.devices) * 1e-9 / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    # per-op and per-program time --------------------------------------
    def op_s(self, match: Callable[[list], bool], *, busiest=False) -> float:
        """Device seconds of the ops ``match`` accepts: the mean over the
        chips, or the largest chip's with ``busiest``."""
        per = [sum(o[2] for o in d.ops if match(o)) * 1e-9
               for d in self.devices]
        return max(per) if busiest else sum(per) / len(per)

    def program_s(self, match: Callable[[str], bool]) -> float:
        """Device seconds of the programs whose name ``match`` accepts
        (mean over the chips)."""
        per = [sum(p[2] for p in d.programs if match(p[0])) * 1e-9
               for d in self.devices]
        return sum(per) / len(per)

    def program_count(self, match: Callable[[str], bool]) -> int:
        """Runs of the matching programs on the first chip."""
        return sum(1 for p in self.devices[0].programs if match(p[0]))

    # idle gaps -----------------------------------------------------------
    def gaps(self) -> List[Tuple[float, float, str]]:
        """Idle gaps of the first chip in the window: (start, seconds,
        innermost host span open at the gap's midpoint, or ``idle``)."""
        d = self.devices[0]
        edges = [self.window[0]] + [t for iv in d.busy for t in iv] + [
            self.window[1]]
        out = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                out.append((s, (e - s) * 1e-9, self.label((s + e) / 2)))
        return out

    def label(self, t: float) -> str:
        best = None
        for name, s, dur in self.spans:
            if s <= t <= s + dur and (best is None or dur < best[1]):
                best = (name, dur)
        return best[0][len(SPAN_PREFIX):] if best else "idle"

    def breakdown(self) -> dict:
        """The ten device ops that took most time (self time: an op that
        holds others, as a ``while`` its body, counts only its own; seconds
        summed by op, mean over the chips) and the ten longest idle
        gaps."""
        tot: Dict[str, float] = {}
        for d in self.devices:
            for o, own in zip(d.ops, _self_times(d.ops), strict=True):
                key = f"{o[0]} ({o[3]}) in {o[4].split('(')[0]}"
                tot[key] = tot.get(key, 0.0) + own * 1e-9 / len(
                    self.devices)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:TOP_N]
        gaps = sorted(self.gaps(), key=lambda g: -g[1])[:TOP_N]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[lab, s] for _, s, lab in gaps]}


def _self_times(ops: List[list]) -> List[float]:
    """Each op's duration less that of the ops nested inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [o[2] for o in ops]
    stack: List[int] = []
    for i in order:
        s = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and s + ops[i][2] <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


def summarize(events: dict, n_devices: Optional[int] = None) -> Summary:
    """Reduce a compact event dict to a :class:`Summary` over its window
    (the first ``n_devices`` chips)."""
    t0, t1 = events["window"]
    devs = events["devices"][:n_devices] if n_devices else events["devices"]
    if not devs:
        raise ValueError("no device plane in the trace")
    out = []
    for d in devs:
        ops, iv = [], []
        for name, s, dur, kind, prog in d["ops"]:
            c = _clip(s, dur, t0, t1)
            if c:
                ops.append([name, c[0], c[1] - c[0], kind, prog])
                iv.append(c)
        busy = _union(iv)
        progs = []
        for name, s, dur in d["programs"]:
            c = _clip(s, dur, t0, t1)
            if c:
                progs.append([name, c[0], c[1] - c[0]])
        if d["ops"] and not ops:
            raise ValueError(f"{d['name']}: no device op inside the host "
                             "window span; the clocks do not line up")
        out.append(Device(d["name"], sum(e - s for s, e in busy), busy, ops,
                          progs))
    spans = [s for s in events["spans"] if _clip(s[1], s[2], t0, t1)]
    return Summary((t0, t1), out, spans)
