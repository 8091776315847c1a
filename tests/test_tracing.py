"""The program's own tracing: host spans on the profiler's clock and
named device scopes in the compiled programs.

* ``Session.run`` and ``LMSession.run`` under ``jax.profiler``: every span
  of :data:`repro.core.instrument.SPAN_NAMES` appears, nested under its
  run (or step) span, per-round spans carry their round, and the run span
  counts the objective calls and the host-to-device operand bytes;
* lowered programs: each of :data:`repro.core.instrument.SCOPE_NAMES`
  names ops of the host chunk program, the mesh program (four virtual
  devices, in a child process) or the LM step.
"""
import gzip
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Problem, Schedule, Session, Topology
from repro.configs.base import ModelConfig
from repro.core import instrument
from repro.core.engine import host as host_mod
from repro.core.engine import lm as lm_mod
from repro.core.engine import plan as plan_mod
from repro.data.lm import lm_batch
from repro.launch.mesh import make_host_mesh
from repro.optim import make_sgd

SRC = str(Path(__file__).resolve().parents[1] / "src")
PREFIX = instrument.SPAN_PREFIX
SESSION_SPANS = [n for n in instrument.SPAN_NAMES if n.startswith("Session.")]
LM_SPANS = [n for n in instrument.SPAN_NAMES if n.startswith("LMSession.")]

LM_CFG = ModelConfig(
    name="tiny", family="dense", num_layers=2, d_model=32, num_heads=4,
    num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64, q_chunk_size=16,
    logits_chunk=16, remat=False, activation_dtype="float32")


def _problem(m=64, d=16):
    X = jax.random.normal(jax.random.PRNGKey(0), (m, d))
    y = jnp.sign(X[:, 0] + 0.1)
    return Problem.svm(X, y, lam=0.1, smoothing=1.0)


TOPO = dict(n_groups=2, workers_per_group=2, m_per_worker=16,
            group_rounds=2, local_steps=8)


def _spans(trace_dir) -> list:
    """(name, start_us, end_us, args) of every ``repro:`` host event in
    the newest trace under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.trace.json.gz"),
                   key=lambda p: p.stat().st_mtime)
    assert files, f"no trace under {trace_dir}"
    out = []
    for ev in json.load(gzip.open(files[-1]))["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args", {})
        name = args.get("long_name", ev["name"])
        if name.startswith(PREFIX):
            out.append((name[len(PREFIX):], ev["ts"], ev["ts"] + ev["dur"],
                        args))
    return out


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("backend", ["vmap", "pallas"])
def test_session_run_spans(tmp_path, backend):
    prob = _problem()
    sess = Session.compile(prob, Topology.two_level(**TOPO), backend=backend)
    warm = sess.run(1, key=jax.random.PRNGKey(1))      # compiles everything
    with jax.profiler.trace(str(tmp_path)):
        res = sess.run(2, warm_start=warm)
        jax.block_until_ready(res.alpha)
    spans = _spans(tmp_path)
    assert {s[0] for s in spans} == set(SESSION_SPANS)
    runs = [s for s in spans if s[0] == "Session.run"]
    assert len(runs) == 1
    run = runs[0]
    assert all(_within(s, run) for s in spans)
    assert run[3]["rounds"] == "2" and run[3]["backend"] == backend
    assert run[3]["first_round"] == "2"        # rounds 2 and 3 of the solve
    assert run[3]["recorded"] == "2"           # one objective call a round
    for name in ("Session.key_upload", "Session.dispatch", "Session.record"):
        assert sorted(s[3]["round"] for s in spans if s[0] == name) == \
            ["2", "3"], name
    plan = sess.plan
    S, n, h = plan.n_ticks, plan.n_leaves, plan.h_max
    # lambda*m, the participation and step masks, one key array a round
    want = 4 + 4 * S * n + 4 * S * n * h + 2 * (S * n * 2 * 4)
    assert int(run[3]["upload_bytes"]) == want


@pytest.mark.parametrize("backend,groups,per_group,pack,padded", [
    ("pallas", 1, 3, 3, 0.0),          # 3 leaves: one program packs all 3
    ("pallas", 3, 4, 8, 0.25),         # 12 leaves in 2 packs of 8: 4 of 16
    ("vmap", 3, 4, None, None),        # no kernel, no packing stats
    ("mesh", 1, 1, 1, 0.0),            # the kernel on a device's one leaf
    ("mesh_ref", 1, 1, None, None)])   # mesh leaves on the XLA reference
def test_session_run_span_counts_leaf_packing(tmp_path, backend, groups,
                                              per_group, pack, padded):
    m_b = 8
    kw = ({"backend": "mesh", "mesh_use_kernel": False}
          if backend == "mesh_ref" else {"backend": backend})
    sess = Session.compile(
        _problem(m=groups * per_group * m_b),
        Topology.two_level(groups, per_group, m_b, group_rounds=1,
                           local_steps=4), **kw)
    sess.run(1, key=jax.random.PRNGKey(1))
    with jax.profiler.trace(str(tmp_path)):
        sess.run(1, key=jax.random.PRNGKey(1))
    run = next(s for s in _spans(tmp_path) if s[0] == "Session.run")
    if pack is None:
        assert "leaf_pack" not in run[3]
        assert "leaf_slots_padded" not in run[3]
    else:
        assert int(run[3]["leaf_pack"]) == pack
        assert float(run[3]["leaf_slots_padded"]) == padded


def test_session_run_spans_cold_start_records_round_zero(tmp_path):
    sess = Session.compile(_problem(), Topology.two_level(**TOPO))
    sess.run(1, key=jax.random.PRNGKey(1))
    with jax.profiler.trace(str(tmp_path)):
        sess.run(1, key=jax.random.PRNGKey(1))
    spans = _spans(tmp_path)
    run = next(s for s in spans if s[0] == "Session.run")
    assert run[3]["first_round"] == "1" and run[3]["recorded"] == "2"
    assert sorted(s[3]["round"] for s in spans
                  if s[0] == "Session.record") == ["0", "1"]


def test_lm_session_step_spans(tmp_path):
    mesh = make_host_mesh()
    prob = Problem.lm(LM_CFG, make_sgd(lr=0.05, momentum=0.0), batch=4,
                      seq=16, seed=0)
    topo = Topology.from_mesh(mesh, sync_axes=("data",), periods=(2,))
    sess = Session.compile(prob, topo, backend="mesh", mesh=mesh)
    sess.run(steps=1)
    with jax.profiler.trace(str(tmp_path)):
        sess.run(steps=2)
    spans = _spans(tmp_path)
    assert {s[0] for s in spans} == set(LM_SPANS)
    steps = [s for s in spans if s[0] == "LMSession.step"]
    assert sorted(s[3]["step"] for s in steps) == ["1", "2"]
    for s in spans:
        owner = [t for t in steps if t[3]["step"] == s[3]["step"]]
        assert len(owner) == 1 and _within(s, owner[0]), s


def test_span_helper_prefixes_and_keeps_no_clock():
    sp = instrument.span("Session.dispatch", round=3)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert len(set(instrument.SPAN_NAMES)) == len(instrument.SPAN_NAMES)
    src = Path(instrument.__file__).read_text()
    assert "import time" not in src and "perf_counter" not in src


# ---------------------------------------------------------------------------
# device scopes in the lowered programs
# ---------------------------------------------------------------------------
def _op_names(lowered) -> str:
    """Every op's name stack in a lowered program's debug locations."""
    return "\n".join(re.findall(r'loc\("([^"]*)"',
                                lowered.as_text(debug_info=True)))


def _in_scope(scope: str) -> re.Pattern:
    """A name-stack segment ``scope``, bare or wrapped by transforms
    (``vmap(scope)``, ``transpose(jvp(scope))``)."""
    return re.compile(rf"(^|/)([\w.]+\()*{scope}\)*/", re.M)


def _scopes(names: str) -> set:
    return {s for s in instrument.SCOPE_NAMES if _in_scope(s).search(names)}


def _host_args(sess):
    plan, prob = sess.plan, sess.problem
    keys = plan_mod.chunked_key_plan(sess.resolved.chunk_tree, plan,
                                     jax.random.PRNGKey(0), 1)[0]
    return (prob.X, prob.y, jnp.asarray(keys), jnp.zeros(prob.m),
            jnp.zeros(prob.d), jnp.asarray(plan_mod.full_participation(plan)),
            jnp.asarray(plan_mod.full_steps(plan)),
            host_mod.regularizer_scale(prob.lam, prob.m, prob.X.dtype))


def _host_program(compression, backend, record_history):
    sess = Session.compile(_problem(), Topology.two_level(**TOPO),
                           Schedule(compression=compression))
    fn = host_mod.get_host_executor(sess.plan, loss=sess.problem.loss,
                                    record_history=record_history,
                                    backend=backend)
    return fn.lower(*_host_args(sess))


@pytest.mark.parametrize("backend", ["vmap", "pallas"])
def test_host_chunk_program_scopes(backend):
    lowered = _host_program("none", backend, record_history=False)
    names = _op_names(lowered)
    assert re.search(r"^jit\(solve_fn\)/", names, re.M)
    assert _scopes(names) == {"reblock", "leaf_solve", "level_sync"}
    if backend == "pallas":
        # the kernel's ops run under its name, inside the leaf solve
        assert re.search(r'op_name="[^"]*/leaf_solve/sdca/',
                         lowered.compile().as_text())


def test_host_chunk_program_codec_and_objective_scopes():
    names = _op_names(_host_program("int8", "vmap", record_history=True))
    assert _scopes(names) == {"reblock", "leaf_solve", "level_sync",
                              "codec", "objective"}
    # the codec runs inside the level sync
    assert all(_in_scope("level_sync").search(n) for n in names.splitlines()
               if _in_scope("codec").search(n))


def test_lm_step_scopes():
    opt = make_sgd(lr=0.05, momentum=0.0)
    step = lm_mod.build_lm_step(LM_CFG, opt, level_sizes=(2,))
    state = lm_mod.init_lm_state(LM_CFG, opt, jax.random.PRNGKey(0), 2)
    batch = lm_mod.split_batch(lm_batch(LM_CFG, 4, 16, 0, seed=0), 2)
    names = _op_names(jax.jit(step).lower(
        state, batch, jnp.asarray([2], jnp.int32)))
    assert _scopes(names) == {"forward_backward", "optimizer", "tree_sync"}


MESH_CHILD = r"""
import json, re, sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.api import Problem, Schedule, Session, Topology
from repro.core import instrument
from repro.core.engine import mesh as mesh_mod, plan as plan_mod

X = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
y = jnp.sign(X[:, 0] + 0.1)
prob = Problem.svm(X, y, lam=0.1, smoothing=1.0)
COLLECTIVE = re.compile(r"/(psum|psum_scatter|reduce_scatter|all_gather)")


def names_of(lowered):
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


def operands(sess):
    plan = sess.plan
    keys = plan_mod.chunked_key_plan(sess.resolved.chunk_tree, plan,
                                     jax.random.PRNGKey(0), 1)[0]
    put = lambda a: jax.device_put(jnp.asarray(a), sess._spec_sharding)
    return (put(keys.transpose(1, 0, 2)),
            put(plan_mod.full_participation(plan).T),
            put(plan_mod.full_steps(plan).transpose(1, 0, 2)),
            jnp.asarray(6.4, jnp.float32))


out = {{}}
for sync in ("psum", "reduce_scatter"):
    for comp in ("none", "int8"):
        sess = Session.compile(
            prob, Topology.two_level(2, 2, 16, group_rounds=2,
                                     local_steps=8),
            Schedule(compression=comp), backend="mesh", mesh_sync=sync,
            mesh_use_kernel=False)
        kys, part, steps, lm = operands(sess)
        if comp == "none":
            lo = sess._fn.lower(sess._Xs, sess._ys,
                                jnp.zeros((4, 16)), jnp.zeros(16), kys,
                                part, steps, lm)
        else:
            se = mesh_mod.get_mesh_executor(
                sess.plan, sess._mesh, axes=sess._mesh_axes,
                loss=prob.loss, use_kernel=False, carry_state=True,
                sync=sync)
            state = se.init(X, jnp.zeros(64), jnp.zeros(16))
            lo = se.step.lower(sess._Xs, sess._ys, state, kys, part, steps,
                               lm)
        names = names_of(lo)
        text = "\n".join(names)
        out[sync + "." + comp] = {{
            "scopes": sorted(s for s in instrument.SCOPE_NAMES
                             if re.search(rf"(^|/){{s}}/", text, re.M)),
            "collectives_in_sync": all(
                re.search(r"(^|/)level_sync/", n) for n in names
                if COLLECTIVE.search(n)),
        }}
print(json.dumps(out))
"""


def test_mesh_program_scopes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c",
                           MESH_CHILD.format(src=SRC)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for sync in ("psum", "reduce_scatter"):
        plain, comp = out[sync + ".none"], out[sync + ".int8"]
        assert plain["scopes"] == ["leaf_solve", "level_sync"], out
        assert comp["scopes"] == ["codec", "leaf_solve", "level_sync"], out
        assert plain["collectives_in_sync"] and comp["collectives_in_sync"]


def test_scope_names_innermost_first():
    # an op nested in several scopes counts in the first: the codec runs
    # inside the level sync, the kernel inside the leaf solve
    assert instrument.SCOPE_NAMES[:3] == ("codec", "level_sync",
                                          "leaf_solve")
    assert np.unique(instrument.SCOPE_NAMES).size == \
        len(instrument.SCOPE_NAMES)
