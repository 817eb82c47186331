"""One cell of the benchmark: set-up, measured window, trace, check.

Everything cell-specific is data found by name (see :mod:`bench`); this
module is the one generic runner.  The order of a run:

1. seeded weights and an input pool, each made on the device in one call;
2. the configuration's family builds the system under test from them
   (for CNNs: plan → artifact → ``GraphExecutor``) and its one batch shape
   is warmed: that is ``setup_s``, counted from process start;
3. the measured window: one client calls the entry back to back, each call
   timed from issue to ``block_until_ready``, until ``--seconds`` pass;
4. with ``--trace 1``, a second, traced window of ``trace_calls`` calls,
   reduced by :mod:`bench.traces`, its device time put under the
   program's unit and role names by :mod:`bench.scopes`, and read by the
   per-layer metrics;
5. the peak device memory is read, the program's state freed, and a sample
   of the window's answers, drawn from the seed, is compared with the
   plain reference, recomputed from the seed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

from bench import scopes, traces

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _load(path: str):
    with open(path) as f:
        return json.load(f)


class Registry:
    """``BENCHMARK.json`` and the files it names, under one root."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = _load(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, "bench")

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        (entry,) = [c for c in self.bench["configs"] if c["name"] == name]
        cfg = _load(os.path.join(self.root, entry["file"]))
        plan_path = os.path.join(os.path.dirname(
            os.path.join(self.root, entry["file"])), cfg["plan"])
        with open(plan_path) as f:
            cfg["plan_text"] = f.read()
        cfg["plan_json"] = json.loads(cfg["plan_text"])
        return cfg

    def traffic(self, name: str) -> dict:
        return _load(os.path.join(self.dir, "workloads", f"{name}.json"))

    def metrics(self, kind: str, cell: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        path = os.path.join(self.dir, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def peak_of(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of one chip; an unknown chip is an error."""
    table = _load(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


def configure_jax(root: str = ROOT) -> None:
    """JAX's persistent compilation cache at the fixed ``.jax_cache/`` of
    the checkout, for every program (however quick to compile), with no
    size limit and so no eviction bookkeeping."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def seed_key(seed: int):
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader may read."""

    trace: object            # bench.traces.Reduced
    work: dict               # the configuration's pinned work counts
    peak: dict               # bench/peaks.json entry of this chip
    batch: int
    calls: int               # calls in the traced window
    images_per_s: float      # of the untraced window
    chips: int
    forwards: int = 1        # forwards of the network per call
    roles: object = None     # bench.scopes.Roles: device time by role, unit
    host: dict | None = None  # host spans by name, [[start_ns, dur_ns]]
    alignment: object = None  # bench.scopes.Alignment of the two clocks

    @classmethod
    def from_trace(cls, rec: dict, window_s: float, kinds: dict,
                   names: dict, **kw):
        """The context of a traced window of ``window_s`` s: ``rec`` from
        ``bench.traces.extract``; ``kinds`` and ``names`` from the compiled
        forward (``bench.traces.kernel_kinds``, ``bench.scopes.op_names``);
        ``kw`` the other fields."""
        host = rec["host"]
        modules = ([m[1:] for m in rec["devices"][0]["modules"]]
                   if rec["devices"] else [])
        return cls(trace=traces.reduce(rec, window_s, kinds),
                   roles=scopes.role_seconds(rec, names), host=host,
                   alignment=scopes.align(host[traces.APPLY],
                                          host[traces.SYNC], modules),
                   **kw)


def _sample(seed: int, n_calls: int, pool: int, want: int) -> list[int]:
    """Call indices to check, drawn from the seed: distinct inputs first."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    slots = rng.permutation(min(pool, n_calls))[:want]
    out = []
    for s in slots:
        reps = (n_calls - 1 - s) // pool
        out.append(int(s + pool * rng.integers(0, reps + 1)))
    return sorted(out)


def rel_err(y, ref) -> np.ndarray:
    """Per image: max |y - ref| over max |ref|."""
    y = np.asarray(y, np.float64).reshape(len(y), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    scale = np.maximum(np.abs(ref).max(axis=1), np.finfo(np.float32).tiny)
    err = np.abs(y - ref).max(axis=1) / scale
    return np.where(np.isfinite(err), err, np.inf)


def reference_outputs(fam, cfg, seed, traffic, picks, passes=None):
    """The plain reference's answers for the picked calls' inputs, with
    weights and inputs made again from the seed: one program, which runs
    the reference over blocks of ``ref_block`` images one after another."""
    import jax
    import jax.numpy as jnp
    P, B, blk = traffic["pool"], traffic["batch"], traffic["ref_block"]
    slots = jnp.asarray(np.asarray(picks) % P)
    n = len(picks) * B
    nb = -(-n // blk)
    plan = cfg["plan_json"]

    def answers(key, slots):
        kw, kx = jax.random.split(key)
        params = fam.init_params(cfg, kw)
        x = fam.make_inputs(cfg, kx, P, B)[slots]
        x = x.reshape((n,) + x.shape[2:])
        x = jnp.pad(x, ((0, nb * blk - n),) + ((0, 0),) * (x.ndim - 1))
        ys = jax.lax.map(
            lambda xb: fam.reference(cfg, params, xb, plan, passes,
                                     traffic=traffic),
            x.reshape((nb, blk) + x.shape[1:]))
        return ys.reshape((nb * blk,) + ys.shape[2:])[:n]
    return np.asarray(jax.jit(answers)(seed_key(seed), slots))


class Cell:
    """A built system under test, ready for measured and traced windows."""

    def __init__(self, reg: Registry, name: str, seed: int):
        import jax
        self.name = name
        self.cell = reg.cell(name)
        self.cfg = reg.config(self.cell["config"])
        self.traffic = reg.traffic(self.cell["traffic"])
        self.fam = importlib.import_module(f"bench.{self.cfg['family']}")
        self.forwards = (self.fam.forwards(self.cfg, self.traffic)
                         if hasattr(self.fam, "forwards") else 1)
        self.precision = jax.default_matmul_precision(
            self.cfg["matmul_precision"])
        kw, kx = jax.random.split(seed_key(seed))
        t = self.traffic
        if (t["loop"], t["clients"]) != ("closed", 1):
            raise ValueError(f"traffic {self.cell['traffic']!r}: only a "
                             f"closed loop with one client is generated")
        clock = Phases()
        with self.precision:
            params = jax.jit(lambda k: self.fam.init_params(self.cfg, k))(kw)
            self.inputs = list(jax.jit(lambda k: tuple(self.fam.make_inputs(
                self.cfg, k, t["pool"], t["batch"])))(kx))
            jax.block_until_ready((params, self.inputs))
            clock.mark("weights and inputs")
            work = tempfile.mkdtemp(prefix="bench_")
            try:
                self.ex = self.fam.build(self.cfg, params,
                                         self.cfg["plan_text"], work, clock,
                                         traffic=t)
            finally:
                shutil.rmtree(work, ignore_errors=True)
            del params
            for i in range(t.get("warm_calls", 2)):
                self.ex.apply(self.inputs[i % t["pool"]]).block_until_ready()
            clock.mark("warm-up")
        log(f"{name}: set-up phases {clock}")

    def window(self, seconds: float):
        """Closed loop, one client: (latencies in s, outputs, window s)."""
        lat, outs = [], []
        P = len(self.inputs)
        with self.precision:
            start = time.perf_counter()
            while True:
                t0 = time.perf_counter()
                y = self.ex.apply(self.inputs[len(lat) % P])
                y.block_until_ready()
                t1 = time.perf_counter()
                lat.append(t1 - t0)
                outs.append(y)
                if t1 - start >= seconds:
                    return lat, outs, t1 - start

    def traced_window(self, calls: int, out_dir: str):
        """``calls`` calls under the profiler; (hlo text, trace file, s)."""
        import glob

        import jax
        with self.precision:
            compiled = self.ex.lower(self.inputs[0]).compile()
            hlo = compiled.as_text()
            mem = compiled.memory_analysis()
            if mem is not None:
                log(f"{self.name}: compiled forward: arguments "
                    f"{mem.argument_size_in_bytes} B, temporaries "
                    f"{mem.temp_size_in_bytes} B, output "
                    f"{mem.output_size_in_bytes} B")
            self.ex.apply(self.inputs[0]).block_until_ready()
            jax.profiler.start_trace(out_dir)
            try:
                t0 = time.perf_counter()
                for i in range(calls):
                    with jax.profiler.TraceAnnotation("bench.issue"):
                        y = self.ex.apply(self.inputs[i % len(self.inputs)])
                    with jax.profiler.TraceAnnotation("bench.sync"):
                        y.block_until_ready()
                window = time.perf_counter() - t0
            finally:
                jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                            recursive=True)
        return hlo, path, window

    def free(self):
        """Drop the program's weights and inputs before the reference runs."""
        del self.ex, self.inputs
        gc.collect()


class Phases:
    """Host-clock seconds of the named phases of set-up, in order."""

    def __init__(self):
        self.t = time.perf_counter()
        self.done: list[tuple[str, float]] = []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.done.append((name, now - self.t))
        self.t = now

    def __str__(self) -> str:
        return ", ".join(f"{n} {s:.3f} s" for n, s in self.done)


class Compiles:
    """Programs built (compiled or read from the persistent cache) and
    persistent-cache hits, counted from construction on."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.hits = 0

        def built(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1
                self.seconds += duration

        def hit(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(built)
        jax.monitoring.register_event_listener(hit)

    def __str__(self) -> str:
        return (f"{self.programs} programs in {self.seconds:.3f} s, "
                f"{self.hits} from the persistent cache")


def run(reg: Registry, name: str, seed: int, seconds: float, trace: bool,
        t_start: float, device: dict) -> dict:
    """One run of one cell; the result object the contract prints."""
    import jax

    compiles = Compiles()
    cell = Cell(reg, name, seed)
    t = cell.traffic
    setup_s = time.perf_counter() - t_start
    n0 = compiles.programs
    log(f"{name}: set-up {setup_s:.3f} s ({compiles}); window {seconds} s")
    lat, outs, window_s = cell.window(seconds)
    in_window = compiles.programs - n0
    images_per_s = len(lat) * t["batch"] / window_s
    log(f"{name}: {len(lat)} calls in {window_s:.3f} s, "
        f"{images_per_s:.3f} images/s, median call "
        f"{statistics.median(lat) * 1e3:.3f} ms, {in_window} compiles in "
        f"the window")
    if in_window:
        log("WARNING: the measured window compiled")
    metrics = {}
    if trace:
        peak = peak_of(device["kind"], reg.root)
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            hlo, path, tw = cell.traced_window(t["trace_calls"], tdir)
            rec = traces.extract(path)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = MetricContext.from_trace(
            rec, tw, traces.kernel_kinds(hlo), scopes.op_names(hlo),
            work=cell.cfg["work"], peak=peak,
            batch=t["batch"], calls=t["trace_calls"],
            images_per_s=images_per_s, chips=cell.cell["chips"],
            forwards=cell.forwards)
        red = ctx.trace
        for line in scopes.role_table(ctx.roles, t["trace_calls"]):
            log(f"{name}: role {line}")
        al = ctx.alignment
        log(f"{name}: host - device clock offset "
            + ("unknown" if al is None else f"{al.offset_ns * 1e-6:.4f} ms "
               f"(interval {al.width_ns * 1e-6:.4f} ms wide)"))
        for m in reg.metrics("per_layer", name):
            v = reg.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = {**device, "busy_s": red.busy_s, "window_s": red.window_s}
        breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
    else:
        e2e = {"images_per_s": images_per_s, "setup_s": setup_s,
               "latency_ms_p95": float(np.percentile(lat, 95)) * 1e3,
               "latency_ms_mean": window_s * 1e3 / len(lat)}
        for m in reg.metrics("end_to_end", name):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:cell.cell["chips"]]]
    if any(peaks):
        device = {**device, "memory_peak_bytes": max(p for p in peaks if p)}

    picks = _sample(seed, len(lat), t["pool"], t["check_calls"])
    y = np.concatenate([np.asarray(outs[i]) for i in picks])
    del outs
    cell.free()
    t0 = time.perf_counter()
    with cell.precision:
        ref = reference_outputs(cell.fam, cell.cfg, seed, t, picks)
    log(f"{name}: reference {time.perf_counter() - t0:.3f} s")
    err = rel_err(y, ref)
    limit = cell.cfg["check"]["max_rel_err"]
    per_call = err.reshape(len(picks), -1).max(axis=1)
    failed = int((~(per_call <= limit)).sum())
    worst = float(err.max())
    result = {"correct": failed == 0, "attempted": len(lat),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = {"max_rel_err": {"value": worst, "limit": limit}}
    log(f"{name}: checked {len(picks)} calls ({len(y)} images) against the "
        f"reference")
    log(f"check max_rel_err {worst!r} limit {limit!r}")
    return result
