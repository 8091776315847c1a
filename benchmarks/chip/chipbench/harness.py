"""The harness: finds a cell's configuration, traffic mix and per-layer
metrics by name, holds the run's clock, spans and compile counter, and
prints the result line.

Nothing here names a cell.  Under ``benchmarks/chip/``, a configuration is
``configs/<name>.json`` (its ``driver`` key picks the family: ``dual`` or
``lm``; an LM configuration's ``reference`` key names its plain reference,
``chipbench/<name>.py``), a traffic mix is ``mixes/<name>.json``, a cell's
correctness limits are ``limits/<workload>.json``, and a per-layer metric
is ``metrics/<name>.py`` with a ``read(ctx)`` function.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                          # the checkout
CACHE_DIR = ROOT / ".jax_cache"                      # fixed: part of the key
TRACE_ROOT = ROOT / ".bench_traces"
SPAN_PREFIX = "bench:"


class BenchError(RuntimeError):
    """A run that cannot produce a result line (exit code 2)."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def load_benchmark(root: Optional[Path] = None) -> dict:
    path = (root or ROOT) / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise BenchError(f"unknown {what} {name!r} (known: {known})")


def find_workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str, root: Optional[Path] = None) -> dict:
    entry = _entry(bench["configs"], name, "configuration")
    path = (root or ROOT) / entry["file"]
    if not path.is_file():
        raise BenchError(f"configuration {name!r}: no file {entry['file']}")
    return json.loads(path.read_text())


def _named_file(kind: str, name: str, suffix: str,
                bench_dir: Optional[Path]) -> Path:
    path = (bench_dir or BENCH_DIR) / kind / f"{name}{suffix}"
    if not path.is_file():
        raise BenchError(f"unknown {kind[:-1]} {name!r}: no {path.name} in "
                         f"{kind}/")
    return path


def load_mix(name: str, bench_dir: Optional[Path] = None) -> dict:
    return json.loads(_named_file("mixes", name, ".json", bench_dir)
                      .read_text())


def load_limits(workload: str, bench_dir: Optional[Path] = None) -> dict:
    """The correctness limits of one cell: ``limits/<workload>.json``."""
    return json.loads(_named_file("limits", workload, ".json", bench_dir)
                      .read_text())["limits"]


def _exec_file(path: Path, prefix: str, name: str) -> ModuleType:
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, bench_dir: Optional[Path] = None) -> Callable:
    """The ``read(ctx)`` of ``metrics/<name>.py``."""
    path = _named_file("metrics", name, ".py", bench_dir)
    return _exec_file(path, "chipbench_metric_", name).read


REFERENCE_API = ("init", "loss_fn", "flops_per_token")


def load_reference(name: str, bench_dir: Optional[Path] = None
                   ) -> ModuleType:
    """The plain reference ``chipbench/<name>.py`` that an LM
    configuration names under ``reference``.  It provides
    ``init(model, seed)`` (float32 weights in the program's tree layout,
    made from the seed by the program's recipe), ``loss_fn(params, batch,
    model, q)`` (the scalar the program's loss reports, ``q`` the
    control's rounding of matmul inputs) and ``flops_per_token(model,
    seq)`` (training FLOPs a token needs, counted from shapes)."""
    path = (bench_dir or BENCH_DIR) / "chipbench" / f"{name}.py"
    if not (name.isidentifier() and path.is_file()):
        raise BenchError(f"unknown reference {name!r}: no {name}.py in "
                         f"chipbench/")
    if path.resolve().parent == Path(__file__).resolve().parent:
        mod = importlib.import_module(f"chipbench.{name}")
    else:
        mod = _exec_file(path, "chipbench_reference_", name)
    missing = [f for f in REFERENCE_API if not callable(getattr(mod, f,
                                                                None))]
    if missing:
        raise BenchError(f"reference {name!r} lacks {', '.join(missing)}")
    return mod


def cell_metrics(bench: dict, workload: str, per_layer: bool) -> List[dict]:
    """The metrics a cell reports: end-to-end ones (``--trace 0``) or
    per-layer ones (``--trace 1``).  A metric with a ``workloads`` list
    belongs to those cells only; one without it to every cell that reports
    the end-to-end metric it moves (end-to-end: to every cell)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ---------------------------------------------------------------------------
# the run's context
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""
    end_to_end: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    counts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # the cell's plain reference module, read by per-layer metrics as
    # ``ctx["reference"]`` (None where the driver has no named reference)
    reference: Optional[ModuleType] = None


class Run:
    """One run of one cell: its inputs, set-up clock, spans and log."""

    def __init__(self, *, workload: dict, config: dict, mix: dict,
                 limits: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, peaks: Optional[dict] = None):
        self.workload = workload
        self.config = config
        self.mix = mix
        self.limits = limits
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.peaks = peaks
        self.setup_split: Dict[str, float] = {}
        self._t_phase = t_start
        self.setup_s: Optional[float] = None
        self.trace_dir: Optional[Path] = None

    # set-up ---------------------------------------------------------------
    def phase(self, name: str) -> None:
        """Close the set-up phase ``name`` (time since the previous one)."""
        now = time.perf_counter()
        self.setup_split[name] = now - self._t_phase
        self._t_phase = now

    def end_setup(self) -> float:
        self.setup_s = time.perf_counter() - self.t_start
        self.log("setup split (s): " + json.dumps(self.setup_split))
        return self.setup_s

    # spans ----------------------------------------------------------------
    def span(self, name: str):
        """A host span on the profiler's clock, around a call into the
        program (only while tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def start_window(self) -> None:
        """Under ``--trace 1``, start profiling and open the window span."""
        if not self.trace:
            return
        import shutil

        import jax
        self.trace_dir = TRACE_ROOT / self.workload["name"]
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.trace_dir))
        self._window_span = jax.profiler.TraceAnnotation(
            SPAN_PREFIX + "window")
        self._window_span.__enter__()

    def stop_window(self) -> None:
        if not self.trace:
            return
        import jax
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def window(self):
        """The measured window; under ``--trace 1`` also the profiled one."""
        self.start_window()
        try:
            yield
        finally:
            self.stop_window()

    @staticmethod
    def log(msg: str) -> None:
        print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while active."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self.active = False

        def on_duration(event, _secs, **_kw):
            if self.active and event in self._EVENTS:
                self.count += 1

        mon.register_event_duration_secs_listener(on_duration)

    @contextlib.contextmanager
    def counting(self):
        self.active = True
        try:
            yield
        finally:
            self.active = False


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def require_chips(chips: int):
    """The devices of this run: at least ``chips`` TPUs, else BenchError.
    Nothing falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's default device is "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def load_peaks(kind: str, bench_dir: Optional[Path] = None) -> dict:
    table = json.loads(((bench_dir or BENCH_DIR) / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no count)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def setup_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache/``; programs that read ``JAX_COMPILATION_CACHE_DIR`` get
    the same directory.  Every program is cached, however quick."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def program_path() -> None:
    """Put the program under test (``src/``) on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: List[Check], breakdown: Optional[dict] = None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)
