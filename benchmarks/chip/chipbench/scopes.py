"""The program's own scopes and spans in a traced window: device time by
``jax.named_scope``, device-idle time by the program's innermost open
host span, and the spans' counters.

:mod:`xplane` reads the ``.xplane.pb`` of a traced window, whose device
events carry an op's HLO name and its timing only: no scope is visible
there.  The same ``jax.profiler`` session also writes a
``*.trace.json.gz`` (Chrome trace format, microseconds on the profiler's
clock) in which each device op carries ``args.tf_op``, its JAX name stack
(``;``-separated where XLA fused several), and the host events include
the program's spans (``repro:<name>``, their stats as event args) and the
benchmark's own (``bench:<name>``).  This module reads that file.

Two stages, as in :mod:`xplane`, so that the second can be checked on a
small recorded window:

* :func:`load` reduces the newest trace file under a directory to a
  compact dict: ``{"window": [t0, t1], "spans": [[name, start, dur,
  {stat: value}], ...], "devices": [{"name", "ops": [[name, start, dur,
  tf_op, program], ...]}]}`` (``repro:`` and ``bench:`` spans only; the
  ``bench:window`` span gives the window);
* :class:`Window` answers the metrics' questions over it.

Scope rule: an op names segment ``s`` when a path segment ``s`` (bare,
or wrapped by a transform as in ``vmap(s)``, never ``jit(s)``) appears in
one of its ``tf_op`` entries.  The partition: an op belongs to the first
scope of :data:`SCOPES` that it names, so the scopes' times sum to the
busy time; ops of the chunk program (the one ``chunk_ms`` matches) in no
scope count as unscoped.  Segments: :meth:`Window.segment_ms` reads the
ops that name any given segment, whatever their partition scope, so a
scope nested in another (``forward_backward/moe``) is read without a
place in :data:`SCOPES`.  Nested ops (a ``while`` and its body) count
their self time, so a program's ops sum to its busy time.
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench.xplane import (DEVICE_PLANE, SPAN_PREFIX as BENCH, WINDOW_SPAN,
                              _clip, _self_times, _union)

REPRO = "repro:"
SCOPES = ("codec", "level_sync", "leaf_solve", "reblock", "objective",
          "forward_backward", "optimizer", "tree_sync")
CHUNK_PROGRAMS = ("solve_fn", "program")       # as chunk_ms matches them
# the spans whose count a per-round (per-step) metric divides by
UNIT_SPANS = (REPRO + "Session.run", REPRO + "LMSession.step")
OPS_THREAD, PROGRAMS_THREAD = "XLA Ops", "XLA Modules"
# a scope's segment, bare or wrapped by the transforms that rename a
# name stack's segments (``vmap(s)``, ``transpose(jvp(s))``)
_WRAP = r"(?:(?:vmap|jvp|transpose|pmap|remat|checkpoint)\()*"


@functools.lru_cache(maxsize=None)
def _segment_re(name: str) -> re.Pattern:
    return re.compile(rf"(^|/){_WRAP}{re.escape(name)}\)*(/|:|$)")


def names_segment(tf_op: str, names) -> bool:
    """Whether ``tf_op`` names any of ``names`` as a path segment."""
    parts = tf_op.split(";")
    return any(_segment_re(n).search(p) for n in names for p in parts)


def scope_of(tf_op: str) -> Optional[str]:
    """The first scope of :data:`SCOPES` that ``tf_op`` names, or None."""
    for s in SCOPES:
        if names_segment(tf_op, (s,)):
            return s
    return None


# ---------------------------------------------------------------------------
# stage 1: the trace file to a compact dict
# ---------------------------------------------------------------------------
def newest_trace(trace_dir) -> Optional[Path]:
    files = sorted(Path(trace_dir).rglob("*.trace.json.gz"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def _span_name(ev: dict) -> str:
    long = ev.get("args", {}).get("long_name", "")
    return long if long.startswith((REPRO, BENCH)) else ev.get("name", "")


def reduce_events(trace: dict, n_devices: Optional[int] = None) -> dict:
    """The compact dict of a parsed Chrome trace (the first ``n_devices``
    chips by ordinal, every chip when None)."""
    procs, threads = {}, {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    chips = {}
    for pid, name in procs.items():
        m = DEVICE_PLANE.match(name)
        if m and (n_devices is None or int(m.group(1)) < n_devices):
            chips[pid] = int(m.group(1))
    ops: Dict[int, list] = {pid: [] for pid in chips}
    progs: Dict[int, list] = {pid: [] for pid in chips}
    spans, last = [], float("-inf")
    for ev in trace["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        last = max(last, ev["ts"])
        pid = ev.get("pid")
        if pid in chips:
            thread = threads.get((pid, ev.get("tid")))
            if thread == OPS_THREAD:
                ops[pid].append([ev["name"], ev["ts"], ev["dur"],
                                 ev.get("args", {}).get("tf_op", "")])
            elif thread == PROGRAMS_THREAD:
                progs[pid].append([ev["ts"], ev["dur"], ev["name"]])
            continue
        name = _span_name(ev)
        if name.startswith((REPRO, BENCH)):
            stats = {k: v for k, v in ev.get("args", {}).items()
                     if k != "long_name"}
            spans.append([name, ev["ts"], ev["dur"], stats])
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w = max(windows, key=lambda s: s[2])
    devices = []
    for pid, n in sorted(chips.items(), key=lambda kv: kv[1]):
        pr = sorted(progs[pid])
        starts = [p[0] for p in pr]
        rows = []
        for name, ts, dur, tf_op in ops[pid]:
            i = bisect.bisect_right(starts, ts) - 1
            prog = pr[i][2] if i >= 0 and ts <= pr[i][0] + pr[i][1] else ""
            rows.append([name, ts, dur, tf_op, prog])
        devices.append({"name": procs[pid], "ops": rows})
    return {"window": [w[1], w[1] + w[2]], "last_event": last,
            "spans": [s for s in spans if s[0] != WINDOW_SPAN],
            "devices": devices}


def _reduce_file(path: Path, n_devices: Optional[int]) -> dict:
    with gzip.open(path, "rt") as f:
        return reduce_events(json.load(f), n_devices)


def load(trace_dir, n_devices: Optional[int] = None) -> Optional[dict]:
    """The compact dict of the newest trace under ``trace_dir``, or None
    where there is none."""
    path = newest_trace(trace_dir)
    return None if path is None else _reduce_file(path, n_devices)


# ---------------------------------------------------------------------------
# stage 2: the questions
# ---------------------------------------------------------------------------
class Window:
    """A compact trace dict clipped to the part of its window it covers.

    The profiler writes at most a fixed number of events to the trace
    file (1,000,000 unless ``TF_PROFILER_TRACE_VIEWER_MAX_EVENTS`` says
    otherwise) and drops the latest ones.  Where the last event it kept
    starts before the window closes, the window is cut at the end of the
    last whole unit span (``repro:Session.run``, ``repro:LMSession.step``)
    before it.  :attr:`units` counts what the covered part holds: root
    rounds (the ``rounds`` stats of its ``Session.run`` spans) and steps
    (its ``LMSession.step`` spans)."""

    def __init__(self, events: dict):
        t0, t1 = events["window"]
        if not events["devices"]:
            raise ValueError("no device process in the trace")
        if events.get("last_event", t1) < t1:
            ends = [s[1] + s[2] for s in events["spans"]
                    if s[0] in UNIT_SPANS and t0 <= s[1]
                    and s[1] + s[2] <= events["last_event"]]
            t1 = max(ends, default=t0)
        self.window = (t0, t1)
        self.covered = (t1 - t0) / (events["window"][1] - t0)
        whole = [s for s in events["spans"] if s[0] in UNIT_SPANS
                 and t0 <= s[1] and s[1] + s[2] <= t1]
        self.units = {
            "rounds": sum(int(s[3].get("rounds", 0)) for s in whole
                          if s[0] == UNIT_SPANS[0]),
            "steps": sum(1 for s in whole if s[0] == UNIT_SPANS[1])}
        # per chip: [scope or None, self us, program, name stack] and busy
        # intervals; the name stacks repeat a lot, so each is kept once, in
        # :attr:`stacks` with its partition scope
        self.ops: List[List[tuple]] = []
        self.busy: List[List[Tuple[float, float]]] = []
        self.stacks: Dict[str, Optional[str]] = {}
        for d in events["devices"]:
            clipped = []
            for name, ts, dur, tf_op, prog in d["ops"]:
                c = _clip(ts, dur, t0, t1)
                if c:
                    clipped.append([name, c[0], c[1] - c[0], tf_op, prog])
            own = _self_times(clipped)
            for o in clipped:
                o[3] = sys.intern(o[3])
                if o[3] not in self.stacks:
                    self.stacks[o[3]] = scope_of(o[3])
            self.ops.append([(self.stacks[o[3]], t, o[4], o[3])
                             for o, t in zip(clipped, own, strict=True)])
            self.busy.append(_union([(o[1], o[1] + o[2]) for o in clipped]))
        self.spans = [s for s in events["spans"]
                      if _clip(s[1], s[2], t0, t1)]

    @property
    def n_chips(self) -> int:
        return len(self.ops)

    # device time by scope ---------------------------------------------
    def scope_ms(self, scope: Optional[str], *, chunk_only=False
                 ) -> List[float]:
        """Device milliseconds per chip of the ops in ``scope`` (None: in
        no scope), of the chunk program alone with ``chunk_only``."""
        out = []
        for ops in self.ops:
            out.append(1e-3 * sum(
                t for sc, t, prog, _ in ops if sc == scope and (
                    not chunk_only
                    or any(p in prog for p in CHUNK_PROGRAMS))))
        return out

    def has_scopes(self) -> bool:
        return any(sc is not None for ops in self.ops for sc, *_ in ops)

    def naming(self, names) -> set:
        """The window's name stacks that name any of ``names`` as a path
        segment (the scope rule of this module)."""
        return {st for st in self.stacks if names_segment(st, names)}

    def segment_ms(self, names) -> List[float]:
        """Device milliseconds per chip of the ops whose name stack names
        any of ``names`` as a path segment, whatever their partition
        scope (an op counts once, however many of them it names)."""
        hit = self.naming(names)
        return [1e-3 * sum(t for _, t, _, st in ops if st in hit)
                for ops in self.ops]

    # device idle by innermost span ----------------------------------------
    def _labelled(self, prefix: str) -> List[Tuple[float, float, str]]:
        """The window cut at every boundary of a ``prefix`` span, each
        piece labelled with the innermost span open over it (the latest
        opened; '' where none is)."""
        t0, t1 = self.window
        spans = [s for s in self.spans if s[0].startswith(prefix)]
        cuts = sorted({t0, t1} | {min(max(t, t0), t1) for s in spans
                                  for t in (s[1], s[1] + s[2])})
        starts = sorted(spans, key=lambda s: s[1])
        out, active, j = [], [], 0
        for a, b in zip(cuts, cuts[1:]):
            while j < len(starts) and starts[j][1] <= a:
                active.append(starts[j])
                j += 1
            active = [s for s in active if s[1] + s[2] > a]
            inner = max(active, key=lambda s: (s[1], -s[2]))[0] \
                if active else ""
            out.append((a, b, inner))
        return out

    def idle_ms_by_span(self, prefix: str = REPRO) -> Dict[str, float]:
        """Device-idle milliseconds in the window by the innermost open
        span whose name starts with ``prefix`` ('' where none is open),
        the mean over the chips."""
        pieces = self._labelled(prefix)
        tot: Dict[str, float] = {}
        for busy in self.busy:
            t0, t1 = self.window
            edges = [t0] + [t for iv in busy for t in iv] + [t1]
            idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s]
            i = 0
            for s, e in idle:
                while i < len(pieces) and pieces[i][1] <= s:
                    i += 1
                k = i
                while k < len(pieces) and pieces[k][0] < e:
                    c = _clip(s, e - s, pieces[k][0], pieces[k][1])
                    if c:
                        name = pieces[k][2]
                        tot[name] = tot.get(name, 0.0) + \
                            1e-3 * (c[1] - c[0]) / self.n_chips
                    k += 1
        return tot

    # counters -------------------------------------------------------------
    def stat_sums(self, name: str) -> Dict[str, int]:
        """Stats of the window's spans called ``name``, summed where they
        read as whole numbers."""
        out: Dict[str, int] = {}
        for s in self.spans:
            if s[0] != name or not (self.window[0] <= s[1]
                                    <= self.window[1]):
                continue
            for k, v in s[3].items():
                try:
                    out[k] = out.get(k, 0) + int(v)
                except (TypeError, ValueError):
                    pass
        return out


@functools.lru_cache(maxsize=1)
def _window(path: str, stamp: tuple, n_devices: int) -> Optional[Window]:
    try:
        return Window(_reduce_file(Path(path), n_devices))
    except ValueError:          # no window span or no device in the file
        return None


def window_of(ctx) -> Optional[Window]:
    """The traced window of the run in ``ctx``: the newest trace file
    under ``harness.TRACE_ROOT / <cell>``, read once per process and file
    (every metric of a run shares it), or None where there is none."""
    from chipbench import harness
    path = newest_trace(harness.TRACE_ROOT / ctx["workload"]["name"])
    if path is None:
        return None
    st = path.stat()
    return _window(str(path), (st.st_mtime_ns, st.st_size),
                   int(ctx["workload"]["chips"]))


def scope_ms_per(ctx, scopes, per: str, chunk_only=False
                 ) -> Optional[float]:
    """Device ms per ``per`` (``"rounds"`` or ``"steps"``, counted from
    the program's unit spans) of the ops in any of ``scopes`` (None: in no
    scope) on the busiest chip; None where the trace has no such span or
    names no scope at all (a program without them)."""
    win = window_of(ctx)
    if win is None or not win.has_scopes() or not win.units[per]:
        return None
    per_chip = [sum(ms) for ms in zip(*(
        win.scope_ms(sc, chunk_only=chunk_only) for sc in scopes),
        strict=True)]
    return max(per_chip) / win.units[per]


def segment_ms_per(ctx, names, per: str) -> Optional[float]:
    """Device ms per ``per`` (as in :func:`scope_ms_per`) of the ops whose
    name stack names any of ``names`` as a path segment, nested in a
    partition scope or not (:meth:`Window.segment_ms`), on the busiest
    chip; None where the trace has no unit span of the program or no op
    names such a segment."""
    win = window_of(ctx)
    if win is None or not win.units[per] or not win.naming(names):
        return None
    return max(win.segment_ms(names)) / win.units[per]


def idle_ms_per(ctx, names, per: str) -> Optional[float]:
    """Device-idle ms per ``per`` (as in :func:`scope_ms_per`) whose
    innermost open ``repro:`` span is one of ``names`` (a name ending in
    ``.`` stands for every span it begins); None where the trace holds no
    unit span of the program."""
    win = window_of(ctx)
    if win is None or not win.units[per]:
        return None
    got = sum(v for k, v in win.idle_ms_by_span().items() if any(
        k == REPRO + n or (n.endswith(".") and k.startswith(REPRO + n))
        for n in names))
    return got / win.units[per]
