"""The benchmark's harness: one run of one cell, driven by data.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything that belongs to one of them
lives in files of its own, found by name:

  configs/<config>.json        sizes, precision, source (``file`` in the spec)
  reference/<reference>.py     the plain reference the configuration names
  programs/<architecture>.py   the program's network the configuration names
  traffic/<traffic>.json       parameters of the mix; ``generator`` names
                               the general generator that reads them
  generators/<generator>.py    set-up, warm-up, window and comparison
  limits/<workload>.json       the limit of each number compared
  layer_metrics/<metric>.py    one reader per per-layer metric

A generator gets a ``Run`` and returns an ``Outcome``; the harness turns that
into the result line.  The harness never imports the program's own
measurement code: it times with the host clock, counts compiles with JAX's
monitoring events and reads the device from the profiler's trace.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import tempfile
import time
import zlib
from dataclasses import dataclass, field, replace
from pathlib import Path

# host spans that mark the traced slice's ends on the profiler's clock
SLICE_START, SLICE_STOP = "bench.slice_start", "bench.slice_stop"


class BenchError(Exception):
    """The run cannot go ahead (no chip, a spec or file missing)."""


def load_json(path: Path):
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    bench: Path

    def reference(self):
        return load_module(self.bench / "reference"
                           / f"{self.config['reference']}.py",
                           f"bench_reference_{self.config['reference']}")

    def generator(self):
        name = self.traffic["generator"]
        return load_module(self.bench / "generators" / f"{name}.py",
                           f"bench_generator_{name}")


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def load_cell(workload: str, root: Path) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with its
    configuration, traffic, limits and the metrics it reports."""
    spec_path = root / "BENCHMARK.json"
    if not spec_path.is_file():
        raise BenchError(f"no BENCHMARK.json in {root}")
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[w["config"]]
    bench = root / spec["paths"][0]
    config = load_json(root / entry["file"])
    arch = config.get("architecture")
    if arch is None:
        raise BenchError(f"configuration {w['config']!r} names no "
                         "architecture")
    if not (bench / "programs" / f"{arch}.py").is_file():
        raise BenchError(f"configuration {w['config']!r} names architecture "
                         f"{arch!r}, and there is no programs/{arch}.py")
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (_applies(m, workload) if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=config,
                traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(bench / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer, bench=bench)


# ---------------------------------------------------------------------------
# compile counting (jax.monitoring events)
# ---------------------------------------------------------------------------

class CompileEvents:
    """Counts XLA compiles and persistent-cache hits (``jax.monitoring``
    events), so a window can say how many programs it compiled or loaded
    after set-up."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiles = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        if event == self.HIT:
            self.hits += 1

    def _on_duration(self, event: str, duration: float, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.seconds += duration

    def mark(self) -> tuple[int, int, float]:
        return self.compiles, self.hits, self.seconds


# ---------------------------------------------------------------------------
# the run a generator is handed
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared with the reference: correct when ``value`` is
    finite and at most ``limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Outcome:
    attempted: int
    failed: int
    values: dict                    # end-to-end metric name -> value
    checks: list                    # [Check]
    notes: list = field(default_factory=list)   # lines for stderr
    # the control and any planted faults: {variant: {number: value}},
    # each read as the program's numbers are
    controls: dict = field(default_factory=dict)
    stats: dict = field(default_factory=dict)    # diagnostics


def stream_key(seed_key, stream: str):
    """The key of the named stream under ``seed_key``; callable inside a
    jitted function, so that one call makes a run's weights and inputs."""
    import jax
    return jax.random.fold_in(seed_key, zlib.crc32(stream.encode()))


class Run:
    """What a generator needs from the harness: the seed's random streams,
    the clock of set-up and window, and the traced slice."""

    # set-up's parts, in the order they run; what none of them covers is
    # reported as the rest
    PHASES = (("jax_start_s", "JAX and TPU start"),
              ("data_s", "weights and data"),
              ("compile_s", "compile"),
              ("warmup_s", "warm-up"),
              ("job_start_s", "job start"))

    def __init__(self, cell: Cell, *, seed: int, seconds: float,
                 trace: bool, t_start: float, host: dict | None = None):
        import jax
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.seed_key = jax.random.fold_in(
            jax.random.PRNGKey(self.seed & 0xFFFFFFFF), self.seed >> 32)
        self.host: dict = dict({"compile_s": 0.0, "warmup_s": 0.0},
                               **(host or {}))
        self.compiles = CompileEvents()
        self.phase_compiles: dict = {}  # phase -> (compiles, hits, seconds)
        self.setup_s = None
        self.window = None              # (t0, t1) on the host clock
        self.window_compiles = None
        self.memory: dict = {}          # label -> (bytes in use, peak)
        self.memory_peak_bytes = None
        self.slice = None               # trace directory, once stopped
        self.slice_work: dict = {}
        self._slice_dir = None          # while the profiler runs
        self._slice_t0 = None
        self._slice_done = False
        self.trace_s: dict = {}         # seconds the profiler itself took

    # -- random streams ----------------------------------------------------

    def key(self, stream: str):
        """An independent key per named stream ("weights", "traffic",
        ...), all from ``--seed``."""
        return stream_key(self.seed_key, stream)

    # -- set-up and window -------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """Host time of a set-up phase (``compile_s``, ``warmup_s``), and
        the XLA compiles (persistent-cache loads among them) in it."""
        t0, c0 = time.perf_counter(), self.compiles.mark()
        try:
            yield
        finally:
            self.host[name] = (self.host.get(name, 0.0)
                               + time.perf_counter() - t0)
            self.phase_compiles[name] = tuple(
                b - a for a, b in zip(c0, self.compiles.mark()))

    def read_memory(self, label: str) -> tuple[int, int] | None:
        """Bytes in use and the peak so far on the fullest chip, kept
        under ``label`` for the run's report."""
        import jax
        stats = [d.memory_stats() or {}
                 for d in jax.local_devices()[:self.cell.chips]]
        stats = [s for s in stats if "peak_bytes_in_use" in s]
        if not stats:
            return None
        got = (max(int(s.get("bytes_in_use", 0)) for s in stats),
               max(int(s["peak_bytes_in_use"]) for s in stats))
        self.memory[label] = got
        return got

    def window_start(self, t: float | None = None) -> float:
        """Open the window (now, or at ``t``, taken earlier): set-up ends.
        May be called from another thread."""
        t = time.perf_counter() if t is None else t
        self.setup_s = t - self.t_start
        self._compile_mark = self.compiles.mark()
        self.window = (t, None)
        self.read_memory("window start")
        return t

    def window_end(self, t: float | None = None) -> float:
        """Close the window (now, or at ``t``), count its compiles and read
        the peak device memory (before any reference runs)."""
        t = time.perf_counter() if t is None else t
        self.window = (self.window[0], t)
        c0, h0, _ = self._compile_mark
        self.window_compiles = (self.compiles.compiles - c0,
                                self.compiles.hits - h0)
        got = self.read_memory("after the window")
        self.memory_peak_bytes = got[1] if got else None
        return t

    def setup_parts(self) -> str:
        """Set-up's parts, as one line."""
        parts = [(k, label, self.host[k]) for k, label in self.PHASES
                 if k in self.host]
        rest = self.setup_s - sum(v for _, _, v in parts)

        def compiles(k):
            if k not in self.phase_compiles:
                return ""
            c, h, s = self.phase_compiles[k]
            return f" ({c} XLA compiles, {h} of them cache loads, {s:.3f}s)"
        return ", ".join(f"{label} {v:.3f}s{compiles(k)}" for k, label, v
                         in parts + [(None, "the rest", rest)])

    @property
    def window_s(self) -> float:
        t0, t1 = self.window
        return t1 - t0

    # -- the traced slice --------------------------------------------------

    def profiler_start(self) -> None:
        """Start the profiler ahead of the slice (a no-op unless
        ``--trace 1``), where starting it at the slice would stall it."""
        if not self.trace or self._slice_dir is not None:
            return
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no per-function host events
        options.host_tracer_level = 1       # the benchmark's own spans
        self._slice_dir = tempfile.mkdtemp(prefix="bench-trace-")
        t = time.perf_counter()
        jax.profiler.start_trace(self._slice_dir, profiler_options=options)
        self.trace_s["start"] = time.perf_counter() - t

    def _profiler_stop(self) -> None:
        import jax
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace_s["stop"] = time.perf_counter() - t
        self.slice, self._slice_dir = self._slice_dir, None

    def slice_start(self) -> None:
        """Mark the start of a steady slice of the window, starting the
        profiler for it (a no-op unless ``--trace 1``).  May be called
        from another thread."""
        if not self.trace or self._slice_t0 is not None:
            return
        import jax
        self.profiler_start()
        with jax.profiler.TraceAnnotation(SLICE_START):
            self._slice_t0 = time.perf_counter()

    def slice_stop(self, **work) -> None:
        """Mark the slice's end and stop the profiler; ``work`` is what
        the slice did (``rows``: required rows, ``calls``) for the
        per-layer readers."""
        if not self.slicing:
            return
        import jax
        with jax.profiler.TraceAnnotation(SLICE_STOP):
            wall = time.perf_counter() - self._slice_t0
        self._slice_done = True
        self.slice_work = dict(work, wall_s=wall)
        self._profiler_stop()

    @property
    def slicing(self) -> bool:
        """True between the slice's two marks."""
        return self._slice_t0 is not None and not self._slice_done

    def slice_tick(self, now: float, work) -> None:
        """Called between the window's calls: starts the slice at
        ``trace_at`` (a share of the window) and stops it after
        ``trace_slice_s``; ``work()`` gives what the slice did."""
        if not self.trace or self._slice_done:
            return
        tr = self.cell.traffic
        if self._slice_t0 is None:
            if now >= self.window[0] + self.seconds * tr["trace_at"]:
                self.slice_start()
        elif now >= self._slice_t0 + tr["trace_slice_s"]:
            self.slice_stop(**work())

    def slice_close(self, work) -> None:
        """After the window: end a slice still open, stop the profiler."""
        if self.slicing:
            self.slice_stop(**work())
        if self._slice_dir is not None:
            self._profiler_stop()

    def annotate(self, name: str):
        """A host span on the profiler's clock around a call of the
        window; the trace reduction labels idle gaps with it."""
        import jax
        return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def peaks_for(kind: str, bench: Path) -> dict:
    table = load_json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def layer_readings(cell: Cell, run: Run, outcome: Outcome, summary,
                   peaks: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    from types import SimpleNamespace
    ctx = SimpleNamespace(trace=summary, work=run.slice_work, peaks=peaks,
                          host=run.host, config=cell.config,
                          traffic=cell.traffic, cell=cell.name,
                          reference=cell.reference())
    out = {}
    for m in cell.per_layer:
        reader = load_module(cell.bench / "layer_metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def checks_of(numbers: dict, limits: dict) -> list:
    """A ``Check`` for every number the cell's limits name."""
    return [Check(n, numbers[n], limits[n]) for n in limits]


def control_lines(cell: Cell, run: Run, outcome: Outcome, *,
                  peaks: dict) -> dict:
    """Each control or planted fault of the outcome in the program's
    place: its numbers judged by ``result_line`` against the cell's own
    limits, as ``{variant: {"correct", "checks"}}``."""
    out = {}
    for variant, numbers in outcome.controls.items():
        line = result_line(cell, run, replace(
            outcome, checks=checks_of(numbers, cell.limits)), peaks=peaks)
        out[variant] = {"correct": line["correct"],
                        "checks": line["checks"]}
    return out


def result_line(cell: Cell, run: Run, outcome: Outcome, *, peaks: dict,
                summary=None) -> dict:
    checks = outcome.checks
    correct = (outcome.attempted > 0 and outcome.failed == 0
               and bool(checks) and all(c.ok for c in checks))
    device = device_info()
    device["memory_peak_bytes"] = run.memory_peak_bytes
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed}
    if run.trace:
        line["metrics"] = layer_readings(cell, run, outcome, summary, peaks)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    else:
        values = dict(outcome.values, setup_s=run.setup_s)
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in values]
        if missing:
            raise BenchError(f"the generator reported no {missing}")
        line["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
    line["device"] = device
    if run.trace and summary is not None:
        line["breakdown"] = summary.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line
