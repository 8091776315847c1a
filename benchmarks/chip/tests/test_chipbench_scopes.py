"""The scope and span reader: device time by named scope, device idle by
the innermost program span, the spans' counters, and each new per-layer
metric read through the harness, on a hand-made window (with a planted op
in no scope and an idle gap in no program span), on a trace without the
program's scopes, and on windows recorded on a TPU v5e chip."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

import chipbench_testutil  # noqa: F401  (puts the benchmark on the path)
from chipbench import harness, scopes

DATA = Path(__file__).with_name("data")
SOLVE, OBJ = "jit_solve_fn(1)", "jit__objective(2)"
BODY = "jit(solve_fn)/while/body/closed_call/"

# one chip, window [0, 1000] us, one root round.  Chunk program ops
# (self time): X copy 40 unscoped, reblock 40, the scan's while 140 of its
# own (unscoped), the kernel 200, a level sync 50 and its codec 10; the
# objective program 30 counts in no chunk scope.  Busy: [100, 140),
# [150, 190), [200, 600), [700, 730).
HAND = {
    "window": [0.0, 1000.0],
    "spans": [
        ["bench:Session.run", 5.0, 890.0, {}],
        ["repro:Session.run", 10.0, 880.0,
         {"rounds": "1", "first_round": "4", "backend": "pallas",
          "recorded": "1", "upload_bytes": "3072"}],
        ["repro:Session.key_plan", 20.0, 100.0, {}],
        ["repro:Session.key_upload", 120.0, 10.0, {"round": "4"}],
        ["repro:Session.dispatch", 130.0, 20.0, {"round": "4"}],
        ["repro:Session.materialize", 160.0, 700.0, {}],
    ],
    "devices": [{"name": "/device:TPU:0", "ops": [
        ["copy.1", 100.0, 40.0, "X:", SOLVE],
        ["fusion.5", 150.0, 40.0, "jit(solve_fn)/reblock/gather:", SOLVE],
        ["while", 200.0, 400.0, "", SOLVE],
        ["sdca.1", 210.0, 200.0, BODY + "leaf_solve/sdca/pallas_call:",
         SOLVE],
        ["fusion.9", 420.0, 50.0, BODY + "level_sync/add:", SOLVE],
        ["fusion.10", 480.0, 10.0, BODY + "level_sync/codec/round:", SOLVE],
        ["fusion.1", 700.0, 30.0, "jit(_objective)/dot_general:", OBJ],
    ]}],
}


def test_scope_rule():
    assert scopes.scope_of(BODY + "leaf_solve/sdca/pallas_call:") == \
        "leaf_solve"
    # nested scopes count in the first of the fixed order
    assert scopes.scope_of(BODY + "level_sync/codec/round:") == "codec"
    # a transform wraps the scope's segment
    assert scopes.scope_of(
        "jit(step)/vmap(forward_backward)/jvp()/while/body/dot_general:") \
        == "forward_backward"
    assert scopes.scope_of(
        "jit(step)/transpose(jvp(forward_backward))/mul:") == \
        "forward_backward"
    assert scopes.scope_of("jit(step)/vmap(optimizer)/add:") == "optimizer"
    # any ;-separated entry of a fused op
    assert scopes.scope_of("jit(solve_fn)/add;jit(solve_fn)/reblock/mul:") \
        == "reblock"
    # a longer name, a primitive or a program of the same stem is none
    for tf_op in ("jit(solve_fn)/leaf_solve_extra/add:", "X:",
                  "jit(solve_fn)/gather:", "jit(objective)/dot:", ""):
        assert scopes.scope_of(tf_op) is None, tf_op


def test_device_time_by_scope_sums_to_the_chunk_program():
    w = scopes.Window(HAND)
    ms = {s: w.scope_ms(s, chunk_only=True)[0] * 1e3
          for s in scopes.SCOPES + (None,)}
    assert ms["leaf_solve"] == pytest.approx(200.0)
    assert ms["reblock"] == pytest.approx(40.0)
    assert ms["level_sync"] + ms["codec"] == pytest.approx(60.0)
    # planted: the X copy and the while's own time are in no scope
    assert ms[None] == pytest.approx(180.0)
    # the chunk program's busy time, and nothing of the objective's
    assert sum(ms.values()) == pytest.approx(480.0)
    assert w.scope_ms(None)[0] * 1e3 == pytest.approx(210.0)


def test_idle_time_by_innermost_program_span():
    idle = {k: v * 1e3 for k, v in
            scopes.Window(HAND).idle_ms_by_span().items()}
    assert idle == pytest.approx({
        "": 120.0,                      # before the run span, and after it
        "repro:Session.run": 40.0,
        "repro:Session.key_plan": 80.0,
        "repro:Session.dispatch": 10.0,
        "repro:Session.materialize": 240.0,
    })
    # the benchmark's own spans label nothing here
    assert "bench:Session.run" not in idle


def test_span_counters_sum_over_the_window():
    sums = scopes.Window(HAND).stat_sums("repro:Session.run")
    assert sums["upload_bytes"] == 3072 and sums["rounds"] == 1
    assert "backend" not in sums


def test_several_chips_busiest_for_time_mean_for_idle():
    two = json.loads(json.dumps(HAND))
    two["devices"].append({"name": "/device:TPU:1", "ops": [
        ["sdca.1", 0.0, 1000.0, BODY + "leaf_solve/sdca/pallas_call:",
         SOLVE]]})
    w = scopes.Window(two)
    assert max(w.scope_ms("leaf_solve")) * 1e3 == pytest.approx(1000.0)
    assert sum(w.idle_ms_by_span().values()) * 1e3 == \
        pytest.approx(490.0 / 2)


def test_a_trace_cut_short_is_read_up_to_its_last_whole_round():
    """The profiler kept only the events that start before 1,500 of a
    window with two rounds: the window is read up to the end of the first
    round, and per-round metrics divide by that one round."""
    two = json.loads(json.dumps(HAND))
    two["window"] = [0.0, 2000.0]
    two["last_event"] = 1500.0
    second = [[n, t + 1000.0, d, st] for n, t, d, st in HAND["spans"]]
    two["spans"] += second
    two["devices"][0]["ops"] += [[n, t + 1000.0, d, tf, p] for n, t, d, tf, p
                                 in HAND["devices"][0]["ops"]
                                 if t + 1000.0 < 1500.0]
    w = scopes.Window(two)
    assert w.window == (0.0, 890.0) and w.units == {"rounds": 1, "steps": 0}
    assert w.covered == pytest.approx(890.0 / 2000.0)
    assert max(w.scope_ms("leaf_solve")) * 1e3 == pytest.approx(200.0)
    idle = w.idle_ms_by_span()
    assert idle[""] * 1e3 == pytest.approx(10.0)
    assert sum(v for k, v in idle.items() if k) * 1e3 == pytest.approx(370.0)


# ---------------------------------------------------------------------------
# from a Chrome trace file, through the harness's metric files
# ---------------------------------------------------------------------------
def chrome_trace(compact: dict, long_names: bool = True,
                 programs: list = None) -> dict:
    """A profiler trace file's events holding ``compact``: device ops and
    programs (``programs``, ``[name, start, dur]`` on the first chip, or
    one spanning each program's ops) on their threads, host spans on a
    python thread (named as the profiler names events that carry args:
    ``long_name`` holds the prefixed name)."""
    ev = [{"ph": "M", "pid": 701, "name": "process_name",
           "args": {"name": "/host:CPU"}},
          {"ph": "M", "pid": 701, "tid": 9, "name": "thread_name",
           "args": {"name": "python3"}}]
    w0, w1 = compact["window"]
    ev.append({"ph": "X", "pid": 701, "tid": 9, "ts": w0, "dur": w1 - w0,
               "name": "bench:window"})
    # the profiler stops after the window closes
    ev.append({"ph": "X", "pid": 701, "tid": 9, "ts": w1 + 10.0, "dur": 5.0,
               "name": "stop_trace"})
    for name, ts, dur, stats in compact["spans"]:
        e = {"ph": "X", "pid": 701, "tid": 9, "ts": ts, "dur": dur,
             "name": name}
        if stats:
            e["args"] = dict(stats)
            if long_names:
                e["name"] = name.split(":", 1)[1]
                e["args"]["long_name"] = name
        ev.append(e)
    for i, d in enumerate(compact["devices"]):
        pid = 3 + i
        ev += [{"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": d["name"]}},
               {"ph": "M", "pid": pid, "tid": 2, "name": "thread_name",
                "args": {"name": "XLA Modules"}},
               {"ph": "M", "pid": pid, "tid": 3, "name": "thread_name",
                "args": {"name": "XLA Ops"}}]
        progs = {}
        for name, ts, dur, tf_op, prog in d["ops"]:
            args = {"tf_op": tf_op} if tf_op else {}
            ev.append({"ph": "X", "pid": pid, "tid": 3, "ts": ts,
                       "dur": dur, "name": name, "args": args})
            lo, hi = progs.get(prog, (ts, ts + dur))
            progs[prog] = (min(lo, ts), max(hi, ts + dur))
        rows = [[p, lo, hi - lo] for p, (lo, hi) in progs.items()]
        for prog, ts, dur in (programs if programs and i == 0 else rows):
            ev.append({"ph": "X", "pid": pid, "tid": 2, "ts": ts,
                       "dur": dur, "name": prog})
    return {"displayTimeUnit": "ns", "traceEvents": ev}


def _write(root: Path, cell: str, trace: dict) -> None:
    d = root / cell / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(trace, f)


def _read_metrics(monkeypatch, tmp_path, compact, *, chips=1, counts,
                  names, long_names=True, programs=None):
    monkeypatch.setattr(harness, "TRACE_ROOT", tmp_path)
    scopes._window.cache_clear()
    _write(tmp_path, "cell", chrome_trace(compact, long_names, programs))
    ctx = {"workload": {"name": "cell", "chips": chips}, "counts": counts}
    out = {n: harness.load_metric(n)(ctx) for n in names}
    scopes._window.cache_clear()
    return out


DUAL_METRICS = ("leaf_solve_ms", "reblock_ms", "level_sync_ms",
                "chunk_unscoped_ms", "key_plan_idle_ms", "session_idle_ms",
                "upload_kb")
LM_METRICS = ("lm_fwd_bwd_ms", "lm_optimizer_ms", "lm_host_idle_ms")


@pytest.mark.parametrize("long_names", [True, False])
def test_reduce_events_round_trip(long_names):
    got = scopes.reduce_events(chrome_trace(HAND, long_names))
    assert got["window"] == HAND["window"]
    assert [s[:3] for s in got["spans"]] == [s[:3] for s in HAND["spans"]]
    assert got["spans"][1][3]["upload_bytes"] == "3072"
    assert got["devices"] == HAND["devices"]


def test_dual_metrics_read_the_hand_window(monkeypatch, tmp_path):
    got = _read_metrics(monkeypatch, tmp_path, HAND, counts={"rounds": 1},
                        names=DUAL_METRICS)
    assert got == pytest.approx({
        "leaf_solve_ms": 0.2, "reblock_ms": 0.04, "level_sync_ms": 0.06,
        "chunk_unscoped_ms": 0.18, "key_plan_idle_ms": 0.08,
        "session_idle_ms": 0.37, "upload_kb": 3.0})


def test_new_metrics_are_silent_on_a_program_without_spans_or_scopes(
        monkeypatch, tmp_path):
    """The program before its spans and scopes: the trace holds the
    benchmark's spans and unscoped ops only, and every new metric reports
    nothing (and raises nothing)."""
    bare = json.loads(json.dumps(HAND))
    bare["spans"] = [s for s in bare["spans"] if s[0].startswith("bench:")]
    for op in bare["devices"][0]["ops"]:
        op[3] = op[3].replace("leaf_solve/", "").replace(
            "level_sync/", "").replace("codec/", "").replace("reblock/", "")
    got = _read_metrics(monkeypatch, tmp_path, bare,
                        counts={"rounds": 1, "steps": 1},
                        names=DUAL_METRICS + LM_METRICS)
    assert got == dict.fromkeys(DUAL_METRICS + LM_METRICS)


def test_metrics_with_no_trace_file_report_nothing(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_ROOT", tmp_path)
    scopes._window.cache_clear()
    ctx = {"workload": {"name": "none", "chips": 1}, "counts": {"rounds": 1}}
    assert harness.load_metric("leaf_solve_ms")(ctx) is None
    assert harness.load_metric("upload_kb")(ctx) is None


# ---------------------------------------------------------------------------
# windows recorded on the chip
# ---------------------------------------------------------------------------
def _recorded(name: str) -> dict:
    path = DATA / name
    if not path.exists():
        pytest.fail(f"missing recorded window {path}")
    return json.loads(gzip.decompress(path.read_bytes()))


@pytest.mark.parametrize("name,metrics", [
    ("pallas_scopes.json.gz", DUAL_METRICS),
    ("vmap_scopes.json.gz", DUAL_METRICS),
    ("lm_scopes.json.gz", LM_METRICS),
])
def test_recorded_window(monkeypatch, tmp_path, name, metrics):
    """Each metric on a few rounds (steps) cut from a traced chip window,
    against values counted apart from the reader."""
    rec = _recorded(name)
    got = _read_metrics(monkeypatch, tmp_path, rec["events"],
                        counts=rec["counts"], names=metrics,
                        programs=rec["programs"])
    assert got == pytest.approx(rec["expected"], rel=1e-3, abs=1e-4)
    if "chunk_ms" in rec:
        parts = sum(got[n] for n in ("leaf_solve_ms", "reblock_ms",
                                     "level_sync_ms", "chunk_unscoped_ms"))
        assert parts == pytest.approx(rec["chunk_ms"], rel=0.01)
        if rec["sdca_kernel_ms"] > 0:
            assert got["leaf_solve_ms"] >= rec["sdca_kernel_ms"]


def test_recorded_window_with_a_planted_op(monkeypatch, tmp_path):
    """An op in no scope, planted in the chunk program inside a key-plan
    idle gap, moves its time from ``key_plan_idle_ms`` to
    ``chunk_unscoped_ms``."""
    rec = _recorded("pallas_scopes.json.gz")
    ev, n = rec["events"], rec["counts"]["rounds"]
    w = scopes.Window(ev)
    plan = next(s for s in ev["spans"] if s[0] == "repro:Session.key_plan")
    edges = [t for iv in w.busy[0] for t in iv]
    gap = next((s, e) for s, e in zip(edges[1::2], edges[2::2])
               if s >= plan[1] and e <= plan[1] + plan[2] and e - s > 150)
    t = gap[0] + 10.0
    chunk = next(p[0] for p in rec["programs"] if "solve_fn" in p[0])
    ev["devices"][0]["ops"].append(["planted", t, 100.0,
                                    "jit(solve_fn)/planted:", chunk])
    got = _read_metrics(monkeypatch, tmp_path, ev, counts=rec["counts"],
                        names=("chunk_unscoped_ms", "key_plan_idle_ms"),
                        programs=rec["programs"] + [[chunk, t, 100.0]])
    want = rec["expected"]
    assert got["chunk_unscoped_ms"] == pytest.approx(
        want["chunk_unscoped_ms"] + 0.1 / n, rel=1e-3)
    assert got["key_plan_idle_ms"] == pytest.approx(
        want["key_plan_idle_ms"] - 0.1 / n, rel=1e-3)


def test_metrics_on_a_trace_without_window_or_chip_report_nothing(
        monkeypatch, tmp_path):
    trace = chrome_trace(HAND)
    no_chip = {"traceEvents": [e for e in trace["traceEvents"]
                               if e.get("pid") == 701]}
    no_window = {"traceEvents": [e for e in trace["traceEvents"]
                                 if e.get("name") != "bench:window"]}
    for i, t in enumerate((no_chip, no_window)):
        monkeypatch.setattr(harness, "TRACE_ROOT", tmp_path / str(i))
        _write(tmp_path / str(i), "cell", t)
        ctx = {"workload": {"name": "cell", "chips": 1},
               "counts": {"rounds": 1}}
        assert harness.load_metric("session_idle_ms")(ctx) is None


# ---------------------------------------------------------------------------
# nested scopes: read by segment, the partition left as it is
# ---------------------------------------------------------------------------
FB, STEP = "jit(step)/vmap(forward_backward)/", "jit_step(3)"
# one chip, one LM step; self times (us): the expert FFN 50 forward and 30
# transposed, attention 40, an op fused from both 15, the rest of the loss
# 20, the optimizer 25, and 10 under a longer name that is no segment
NESTED = {
    "window": [0.0, 1000.0],
    "spans": [["repro:LMSession.step", 10.0, 900.0, {"step": "1"}]],
    "devices": [{"name": "/device:TPU:0", "ops": [
        ["fusion.1", 100.0, 50.0, FB + "jvp()/moe/dot_general:", STEP],
        ["fusion.2", 200.0, 30.0, FB + "transpose(jvp(moe))/dot_general:",
         STEP],
        ["fusion.3", 300.0, 40.0, FB + "attn/dot_general:", STEP],
        ["fusion.4", 400.0, 20.0, FB + "add:", STEP],
        ["fusion.5", 500.0, 25.0, "jit(step)/vmap(optimizer)/add:", STEP],
        ["fusion.6", 600.0, 10.0, "jit(step)/moe_extra/add:", STEP],
        ["fusion.7", 700.0, 15.0, FB + "attn/add;" + FB + "moe/mul:", STEP],
    ]}],
}


def _flat(compact: dict) -> dict:
    """``compact`` with the nested segments taken out of the name stacks."""
    flat = json.loads(json.dumps(compact))
    for op in flat["devices"][0]["ops"]:
        for seg in ("transpose(jvp(moe))/", "moe/", "attn/"):
            op[3] = op[3].replace(seg, "")
    return flat


def test_nested_segments_leave_the_partition_as_it_is():
    w, flat = scopes.Window(NESTED), scopes.Window(_flat(NESTED))
    for sc in scopes.SCOPES + (None,):
        assert w.scope_ms(sc) == flat.scope_ms(sc), sc
    assert w.scope_ms("forward_backward")[0] * 1e3 == pytest.approx(155.0)
    seg = {names: w.segment_ms(names)[0] * 1e3 for names in (
        ("moe",), ("attn",), ("moe", "attn"), ("forward_backward",),
        ("optimizer",), ("moe_",), ("step",))}
    assert seg == pytest.approx({
        ("moe",): 95.0, ("attn",): 55.0,
        ("moe", "attn"): 135.0,             # the fused op counts once
        ("forward_backward",): 155.0, ("optimizer",): 25.0,
        ("moe_",): 0.0, ("step",): 0.0})    # jit(step) names no segment
    assert not w.naming(("moe_",)) and len(w.naming(("moe",))) == 3


def test_segment_ms_per_reads_a_nested_scope(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_ROOT", tmp_path)
    scopes._window.cache_clear()
    _write(tmp_path, "cell", chrome_trace(NESTED))
    ctx = {"workload": {"name": "cell", "chips": 1}, "counts": {"steps": 1}}
    try:
        assert scopes.segment_ms_per(ctx, ("moe",), "steps") == \
            pytest.approx(0.095)
        assert scopes.segment_ms_per(ctx, ("attn",), "steps") == \
            pytest.approx(0.055)
        # no op names the segment, or no unit span of that kind: nothing
        assert scopes.segment_ms_per(ctx, ("router",), "steps") is None
        assert scopes.segment_ms_per(ctx, ("moe",), "rounds") is None
        # the partition's metrics read as they did
        got = {n: harness.load_metric(n)(ctx) for n in
               ("lm_fwd_bwd_ms", "lm_optimizer_ms")}
        assert got == pytest.approx({"lm_fwd_bwd_ms": 0.155,
                                     "lm_optimizer_ms": 0.025})
    finally:
        scopes._window.cache_clear()


@pytest.mark.parametrize("name,scope", [
    ("lm_scopes.json.gz", "forward_backward"),
    ("lm_scopes.json.gz", "optimizer"),
    ("pallas_scopes.json.gz", "leaf_solve"),
    ("vmap_scopes.json.gz", "reblock"),
])
def test_recorded_window_segment_equals_its_scope(name, scope):
    """On a window recorded on the chip, a scope that holds no other scope
    of the partition reads the same as a segment as it does as a scope."""
    w = scopes.Window(_recorded(name)["events"])
    assert w.naming((scope,))
    assert w.segment_ms((scope,)) == w.scope_ms(scope)
