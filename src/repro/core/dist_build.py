"""Fault-tolerant distributed table construction (lease-based fan-out).

Table construction is the paper's wall-clock bottleneck and is
embarrassingly parallel (§3.2): every latency bucket is independent.
This module shards the bucket list of a single build across worker
processes and merges their results into tables **bit-identical** to a
single-process build, no matter which workers died when.

Architecture — files, not RPC
-----------------------------
Coordination happens entirely through a shared ``work_dir`` (POSIX
atomic-rename + ``O_EXCL`` primitives), so the same code runs under CI
subprocesses and a multi-host fleet with a shared filesystem:

* ``manifest.json`` — the ordered work-item list (one key per latency
  bucket), written once, atomically, by the coordinator.  An item's id
  is its manifest index; ids name lease and done files.
* ``leases/<id>.json`` — claim = ``O_CREAT|O_EXCL`` create (atomic);
  the lease carries an expiry ``lease_s`` out, renewed only between
  probe attempts — the lease IS the heartbeat deadline.  Stealing an
  expired lease is a tmp-write + ``os.replace`` + read-back
  verification; the loser of a steal race sees the winner's identity on
  read-back and walks away.
* ``shards/<worker>.jsonl`` — each worker's results, fsync'd line
  appends in the exact ``BuildJournal`` record format
  (``{"k","v","p"}``), plus ``{"evt": "steal", ...}`` audit records.
* ``done/<id>`` — completion markers (result durably in a shard).

Execution is **at-least-once** (a straggler may finish an item that was
already stolen and re-done); attribution is **exactly-once**: the merge
reads shards in a fixed order (w0, w1, …, coordinator) and keeps the
first record per key, so the merged record set is a deterministic
function of the shard contents — and under the analytic oracle every
duplicate carries the identical value anyway.  The merged records land
in the coordinator's real :class:`~repro.core.table_cache.BuildJournal`
and the build finishes through ``build_tables(resume=True)``, so
bit-identity with a single-process build follows from the journal-resume
contract already certified in :mod:`repro.core.table_cache`.

Liveness: after every worker has exited (or the deadline passed), the
coordinator executes any unfinished items inline — ignoring leases,
since their holders are dead — and re-executes items whose done marker
exists but whose shard record was lost or corrupted (``repaired``).  A
build therefore completes even if every worker dies instantly.

Fault points (:mod:`repro.testing.faults`): ``dist.claim`` (after a
successful claim), ``dist.item`` (after claim, before execution — a kill
here leaves a lease held with no result: the canonical mid-bucket
death), ``dist.done`` (after the done marker), and
``dist.shard.append`` / ``dist.shard.append.done`` inside every shard
write (``corrupt-shard`` garbles here).  Worker-targeted process actions
(``kill-worker:<idx>@point``) are translated into each worker's
``REPRO_FAULTS`` environment by :func:`repro.testing.faults.worker_env_spec`.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import jax

from repro.testing import faults

from . import probe_engine, table_cache
from .latency import AnalyticTPUOracle, WallClockOracle
from .tables import build_tables, enumerate_probes

#: Module spawned as ``python -m`` for subprocess workers (the launch
#: layer owns the CLI; referenced here as data only).
WORKER_MODULE = "repro.launch.distributed"


class DistBuildError(RuntimeError):
    """A distributed build could not proceed (bad specs, drift, deadline)."""


def check_fanout(oracle) -> None:
    """Refuse a wall-clock fan-out from a process that holds an accelerator.

    Workers run on the CPU, while a parent on a TPU host holds the chip
    (one process per chip): their probes would time the CPU, and the
    tables would be cached under the parent's device.
    """
    backend = jax.default_backend()
    if isinstance(oracle, WallClockOracle) and backend != "cpu":
        raise DistBuildError(
            f"wall-clock probes cannot fan out to worker processes on a "
            f"{backend} host: the chip belongs to this process, and the "
            f"workers would time the CPU; build with workers=0")


@dataclasses.dataclass(frozen=True)
class WorkItem:
    """One distributable unit: a journal key plus its representative
    segment (first-in-enumeration-order for the bucket)."""

    key: str
    seg: object


def latency_work_items(host, method: str = "layermerge",
                       engine: str = "batched") -> list[WorkItem]:
    """The build's latency work-item list, in deterministic order.

    Derived from the SAME enumeration ``build_tables`` uses
    (:func:`repro.core.tables.enumerate_probes`) and keyed exactly as the
    build journal keys its records — ``latb:<sig>`` per shape bucket
    (batched) or ``lat:<i>:<j>:<k>`` per entry (sequential) — so a merged
    shard record is indistinguishable from one the coordinator journaled
    itself.
    """
    probes = enumerate_probes(host, method)
    items: list[WorkItem] = []
    seen: set = set()
    for p in probes:
        seg = p[5]
        if engine == "sequential":
            key = f"lat:{seg.i}:{seg.j}:{seg.k}"
        else:
            key = f"latb:{probe_engine._signature(host, seg)!r}"
        if key not in seen:
            seen.add(key)
            items.append(WorkItem(key, seg))
    return items


# ---------------------------------------------------------------------------
# Cross-process specs (hosts/oracles close over live arrays — they are
# re-created in each worker from a JSON description)
# ---------------------------------------------------------------------------

def resolve_host_spec(spec: dict):
    """``{"factory": "module:function", "kwargs": {...}}`` → (host, params).

    Factories must be seed-deterministic (see :mod:`repro.testing.hosts`);
    the worker cross-checks the rebuilt host's fingerprint against the
    coordinator's manifest, so silent drift fails loudly instead of
    merging garbage.
    """
    factory = str(spec.get("factory", ""))
    mod_name, sep, fn_name = factory.partition(":")
    if not sep or not fn_name:
        raise DistBuildError(
            f'host spec factory must be "module:function", got {factory!r}')
    import importlib

    try:
        fn = getattr(importlib.import_module(mod_name), fn_name)
    except (ImportError, AttributeError) as e:
        raise DistBuildError(f"cannot resolve host factory {factory!r}: {e}")
    return fn(**spec.get("kwargs", {}))


def oracle_spec(oracle) -> dict:
    cfg = dataclasses.asdict(oracle) if dataclasses.is_dataclass(oracle) \
        else {}
    return {"cls": type(oracle).__name__, "cfg": cfg}


def resolve_oracle_spec(spec: dict | None):
    from . import latency

    spec = spec or {"cls": "AnalyticTPUOracle"}
    cls = getattr(latency, str(spec.get("cls", "")), None)
    if not (isinstance(cls, type) and issubclass(cls, latency.LatencyOracle)):
        raise DistBuildError(f"unknown oracle class {spec.get('cls')!r}")
    return cls(**spec.get("cfg", {}))


def probe_spec(cfg) -> dict | None:
    """ProbeConfig → JSON-able dict.  ``fallback_oracle`` does not ship
    (workers journal ``None`` for quarantined buckets; the coordinator's
    resume re-derives the fallback estimate, so the policy object only
    ever matters on the coordinator)."""
    if cfg is None:
        return None
    d = dataclasses.asdict(cfg)
    d.pop("fallback_oracle", None)
    return d


def resolve_probe_spec(spec: dict | None):
    if not spec:
        return None
    return probe_engine.ProbeConfig(**spec)


# ---------------------------------------------------------------------------
# Work-dir primitives: manifest, leases, shards
# ---------------------------------------------------------------------------

def _manifest_path(work_dir: str) -> str:
    return os.path.join(work_dir, "manifest.json")


def read_manifest(work_dir: str) -> dict | None:
    try:
        with open(_manifest_path(work_dir)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError, ValueError) as e:
        raise DistBuildError(f"corrupt manifest in {work_dir!r}: {e}")


def write_manifest(work_dir: str, cache_key: str, items, *,
                   engine: str, method: str,
                   host_fp: str | None = None) -> dict:
    """Publish the ordered work list once, atomically; idempotent for the
    same build, loud for a different one (a stale work dir must not
    silently mix two builds' shards)."""
    payload = {"cache_key": cache_key, "engine": engine, "method": method,
               "host_fp": host_fp, "items": [it.key for it in items]}
    existing = read_manifest(work_dir)
    if existing is not None:
        if existing != payload:
            raise DistBuildError(
                f"work dir {work_dir!r} already holds a manifest for a "
                "different build — use a fresh work dir")
        return existing
    from repro.checkpoint.ckpt import atomic_write_text

    atomic_write_text(_manifest_path(work_dir), json.dumps(payload))
    return payload


def _await_manifest(work_dir: str, wait_s: float = 15.0,
                    poll_s: float = 0.1) -> dict:
    deadline = time.monotonic() + wait_s
    while True:
        m = read_manifest(work_dir)
        if m is not None:
            return m
        if time.monotonic() > deadline:
            raise DistBuildError(f"no manifest appeared in {work_dir!r}")
        time.sleep(poll_s)


class LeaseStore:
    """File-based work-item leases with expiry-driven reassignment.

    A lease is a JSON file ``{"owner", "expires", "epoch"}``.  Claiming a
    free item is atomic (``O_CREAT|O_EXCL``); stealing an expired lease
    bumps the epoch through a tmp-write + ``os.replace`` and then
    re-reads the file — if the read-back shows a different owner/epoch,
    another stealer won the race and this one walks away.  Leases are an
    ordering *optimization*: correctness never depends on mutual
    exclusion (duplicate execution is merged deterministically), so the
    unavoidable read-then-replace window is harmless.
    """

    def __init__(self, work_dir: str, owner: str, lease_s: float):
        self.lease_dir = os.path.join(work_dir, "leases")
        self.done_dir = os.path.join(work_dir, "done")
        os.makedirs(self.lease_dir, exist_ok=True)
        os.makedirs(self.done_dir, exist_ok=True)
        self.owner = owner
        self.lease_s = float(lease_s)

    def _lease(self, item_id: int) -> str:
        return os.path.join(self.lease_dir, f"{item_id}.json")

    @staticmethod
    def _read(path: str) -> dict | None:
        try:
            with open(path) as f:
                rec = json.load(f)
            return rec if isinstance(rec, dict) else None
        except (OSError, json.JSONDecodeError, ValueError):
            return None

    def holder(self, item_id: int) -> str | None:
        rec = self._read(self._lease(item_id))
        return rec.get("owner") if rec else None

    def claim(self, item_id: int) -> tuple[bool, str | None]:
        """Try to lease ``item_id``; returns ``(claimed, stolen_from)``.

        ``stolen_from`` names the previous holder when the claim
        reassigned an expired (or unreadable) lease — the caller records
        that as a ``steal`` event.
        """
        path = self._lease(item_id)
        rec = {"owner": self.owner,
               "expires": time.time() + self.lease_s, "epoch": 1}
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            cur = self._read(path)
            if cur is not None and cur.get("owner") == self.owner:
                self.renew(item_id)          # our own lease: just extend
                faults.hit("dist.claim")
                return True, None
            if cur is not None and \
                    float(cur.get("expires", 0.0)) > time.time():
                return False, None           # live lease held elsewhere
            rec["epoch"] = (int(cur.get("epoch", 0)) + 1) if cur else 1
            tmp = f"{path}.{self.owner}.tmp"
            try:
                with open(tmp, "w") as f:
                    json.dump(rec, f)
                os.replace(tmp, path)
            except OSError:
                return False, None
            back = self._read(path)
            if not back or back.get("owner") != self.owner \
                    or back.get("epoch") != rec["epoch"]:
                return False, None           # lost the steal race
            faults.hit("dist.claim")
            return True, (cur.get("owner", "?") if cur else "?")
        with os.fdopen(fd, "w") as f:
            json.dump(rec, f)
        faults.hit("dist.claim")
        return True, None

    def renew(self, item_id: int) -> bool:
        """Extend our own lease (between probe attempts — the heartbeat).
        False when the lease was stolen from us meanwhile."""
        path = self._lease(item_id)
        cur = self._read(path)
        if cur is None or cur.get("owner") != self.owner:
            return False
        cur["expires"] = time.time() + self.lease_s
        tmp = f"{path}.{self.owner}.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(cur, f)
            os.replace(tmp, path)
        except OSError:
            return False
        return True

    def release(self, item_id: int) -> None:
        cur = self._read(self._lease(item_id))
        if cur is not None and cur.get("owner") != self.owner:
            return                           # not ours to release
        try:
            os.remove(self._lease(item_id))
        except OSError:
            pass

    def mark_done(self, item_id: int) -> None:
        try:
            with open(os.path.join(self.done_dir, str(item_id)), "w") as f:
                f.write(self.owner)
        except OSError:
            pass

    def is_done(self, item_id: int) -> bool:
        return os.path.exists(os.path.join(self.done_dir, str(item_id)))

    def count_done(self, n: int) -> int:
        return sum(1 for i in range(n) if self.is_done(i))


def shard_path(work_dir: str, name: str) -> str:
    return os.path.join(work_dir, "shards", f"{name}.jsonl")


class ShardJournal:
    """One worker's fsync'd result shard (append-only JSONL).

    Result records use the exact :class:`~repro.core.table_cache.BuildJournal`
    format ``{"k","v","p"}`` so the merge drops them straight into the
    coordinator's journal; ``{"evt": ...}`` records share the file as the
    steal/repair audit trail.  Appends go through
    :func:`repro.checkpoint.ckpt.append_journal_line` at fault point
    ``dist.shard.append`` (where ``corrupt-shard`` garbles).
    """

    def __init__(self, work_dir: str, name: str):
        self.name = name
        self.path = shard_path(work_dir, name)
        self._keys: set[str] = set()

    def put(self, key: str, value, provenance: str = "measured") -> None:
        from repro.checkpoint.ckpt import append_journal_line

        append_journal_line(self.path, json.dumps(
            {"k": key, "v": value, "p": provenance}),
            point="dist.shard.append")
        self._keys.add(key)

    def has(self, key: str) -> bool:
        return key in self._keys

    def event(self, kind: str, **fields) -> None:
        from repro.checkpoint.ckpt import append_journal_line

        append_journal_line(self.path, json.dumps({"evt": kind, **fields}),
                            point="dist.shard.append")


def merge_shards(work_dir: str, names) -> tuple[dict, list, int]:
    """Deterministic first-wins merge of shards in the given order.

    Returns ``(records, events, corrupt)`` where ``records`` maps
    journal key → ``(value, provenance, shard_name)``; the first record
    for a key — in shard order, then file order — wins, so the merge is
    a pure function of the shard set (duplicate executions from lease
    steals collapse identically on every rerun).  Unparsable lines
    (torn by a kill, garbled by ``corrupt-shard``) are counted, not
    trusted — the coordinator re-executes whatever they were.
    """
    from repro.checkpoint.ckpt import read_journal_lines

    records: dict[str, tuple] = {}
    events: list[dict] = []
    corrupt = 0
    for name in names:
        for line in read_journal_lines(shard_path(work_dir, name)):
            try:
                rec = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                corrupt += 1
                continue
            if not isinstance(rec, dict):
                corrupt += 1
                continue
            if "evt" in rec:
                events.append(dict(rec, shard=name))
                continue
            if "k" not in rec or "v" not in rec:
                corrupt += 1
                continue
            records.setdefault(
                rec["k"], (rec["v"], rec.get("p", "measured"), name))
    return records, events, corrupt


# ---------------------------------------------------------------------------
# Worker loop
# ---------------------------------------------------------------------------

def run_worker(work_dir: str, worker_id: int, host, params, oracle, *,
               engine: str = "batched", method: str = "layermerge",
               probe_config=None, lease_s: float = 30.0,
               poll_s: float = 0.2, deadline_s: float = 600.0) -> int:
    """Claim-execute-journal until every manifest item is done.

    The worker re-derives the work list from its own rebuilt host and
    cross-checks the manifest (unknown item keys or a fingerprint
    mismatch mean host-spec drift → :class:`DistBuildError`, exit 3 at
    the CLI).  Traversal starts at a per-worker rotation of the manifest
    so concurrent workers mostly claim disjoint items; expired leases
    encountered on later sweeps are stolen and the steal journaled.
    Returns the number of items this worker completed.
    """
    manifest = _await_manifest(work_dir)
    items = latency_work_items(host, method=method, engine=engine)
    by_key = {it.key: it for it in items}
    unknown = [k for k in manifest["items"] if k not in by_key]
    if unknown:
        raise DistBuildError(
            f"worker host does not produce {len(unknown)} manifest "
            f"item(s) (first: {unknown[0]!r}) — host spec drift?")
    fp_fn = getattr(host, "fingerprint", None)
    if fp_fn is not None and manifest.get("host_fp") \
            and fp_fn() != manifest["host_fp"]:
        raise DistBuildError(
            "worker host fingerprint differs from the coordinator's — "
            "host spec drift?")

    n = len(manifest["items"])
    nw = max(1, int(os.environ.get("REPRO_NUM_PROCESSES", "2")) - 1)
    start = (worker_id * n) // nw if n else 0
    order = list(range(start, n)) + list(range(start))

    cfg = probe_config or probe_engine.ProbeConfig()
    stats = probe_engine.EngineStats(engine=engine)
    shard = ShardJournal(work_dir, f"w{worker_id}")
    store = LeaseStore(work_dir, f"w{worker_id}", lease_s)
    completed = 0
    deadline = time.monotonic() + deadline_s
    while True:
        progressed = False
        remaining = [i for i in order if not store.is_done(i)]
        if not remaining:
            return completed
        for i in remaining:
            if store.is_done(i):
                continue
            got, stolen_from = store.claim(i)
            if not got:
                continue
            if store.is_done(i):             # raced with the finisher
                store.release(i)
                continue
            key = manifest["items"][i]
            if stolen_from is not None:
                shard.event("steal", item=key, id=i, prev=stolen_from)
            # A kill here dies holding the lease with no result — the
            # canonical mid-bucket worker death the protocol must absorb.
            faults.hit("dist.item")
            val, flag = probe_engine.probe_segment(
                host, by_key[key].seg, params, oracle,
                probe_config=cfg, stats=stats)
            store.renew(i)
            shard.put(key, None if val is None else float(val), flag)
            store.mark_done(i)
            faults.hit("dist.done")
            store.release(i)
            completed += 1
            progressed = True
        if not progressed:
            if time.monotonic() > deadline:
                raise DistBuildError(
                    "worker deadline exceeded with items still leased "
                    "elsewhere")
            time.sleep(poll_s)


# ---------------------------------------------------------------------------
# Coordinator
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DistReport:
    """What the fan-out did — who completed what, who died, what was
    reassigned or repaired.  ``dead_workers`` includes stragglers killed
    at shutdown after the build completed without them."""

    workers: int = 0
    items: int = 0                     # total work items this build
    journal_prefilled: int = 0         # resumed from the build journal
    completed_by: dict = dataclasses.field(default_factory=dict)
    reassigned: list = dataclasses.field(default_factory=list)
    repaired: list = dataclasses.field(default_factory=list)
    dead_workers: list = dataclasses.field(default_factory=list)
    corrupt_records: int = 0
    coordinator_items: int = 0         # inline fallback executions
    cache_hit: bool = False
    wall_s: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def worker_log_path(work_dir: str, w: int) -> str:
    """Where worker ``w``'s combined stdout/stderr lands — the first
    place to look when a worker shows up in ``DistReport.dead_workers``."""
    return os.path.join(work_dir, "logs", f"w{w}.log")


def _spawn_worker(work_dir: str, w: int, workers: int, host_spec: dict,
                  oracle, probe_config, *, engine: str, method: str,
                  lease_s: float, deadline_s: float,
                  devices: int | None, platform: str):
    from repro.testing.subproc import REPO_ROOT, subprocess_env

    env = subprocess_env(devices=devices, platform=platform,
                         process_id=w + 1, num_processes=workers + 1,
                         faults_spec=faults.worker_env_spec(w))
    argv = [sys.executable, "-m", WORKER_MODULE, "--worker",
            "--dir", work_dir, "--worker-id", str(w),
            "--host-spec", json.dumps(host_spec),
            "--oracle-spec", json.dumps(oracle_spec(oracle)),
            "--engine", engine, "--method", method,
            "--lease-s", str(lease_s), "--deadline-s", str(deadline_s)]
    ps = probe_spec(probe_config)
    if ps:
        argv += ["--probe-spec", json.dumps(ps)]
    log_path = worker_log_path(work_dir, w)
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    log = open(log_path, "w")
    proc = subprocess.Popen(argv, env=env, cwd=REPO_ROOT, stdout=log,
                            stderr=subprocess.STDOUT, text=True)
    proc._log_file = log
    return proc


def _reap(proc, grace_s: float) -> int:
    try:
        proc.communicate(timeout=grace_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
    log = getattr(proc, "_log_file", None)
    if log is not None:
        log.close()
    return proc.returncode


def dist_build_tables(host, *, cache_dir: str, workers: int = 2,
                      host_spec: dict | None = None,
                      method: str = "layermerge", latency_oracle=None,
                      importance="magnitude", base_perf=None, params=None,
                      prune: bool = True, engine: str = "batched",
                      probe_config=None, resume: bool = True,
                      progress=None, work_dir: str | None = None,
                      lease_s: float = 30.0, poll_s: float = 0.2,
                      deadline_s: float = 600.0,
                      serial_spawn: bool = False,
                      worker_devices: int | None = None,
                      worker_platform: str = "cpu",
                      keep_work_dir: bool = False):
    """Build tables with the latency fan-out sharded across ``workers``
    subprocesses; returns ``(Tables, DistReport)``.

    The flow: enumerate work items → skip ones already in the build
    journal (resume) → publish the manifest → spawn workers (each with a
    non-zero process index, so :func:`repro.launch.distributed.is_main`
    gates them out of every publish) → wait for done markers or worker
    exits → execute leftovers inline → merge shards deterministically →
    append the merged records to the real build journal in ONE fsync →
    finish through ``build_tables(resume=True)``, whose journal-replay
    contract makes the result bit-identical to a single-process build.

    Requires a content-addressable build (``host.fingerprint`` + a
    nameable importance): the merge lands under the build's cache key.
    Measured-importance probes (unserializable closures) always run
    coordinator-side inside the final ``build_tables`` — only the
    latency column fans out.  ``workers=0`` degenerates to the local
    build.  ``serial_spawn`` starts worker ``w+1`` only after worker
    ``w`` exited — used by the fault smokes to make kill/steal timing
    deterministic.
    """
    oracle = latency_oracle or AnalyticTPUOracle()
    check_fanout(oracle)
    key = table_cache.cache_key(host, oracle, method, importance,
                                prune=prune, base_perf=base_perf,
                                engine=engine)
    if key is None:
        raise DistBuildError(
            "distributed builds require a content-addressable cache key "
            "(host.fingerprint + nameable importance): worker results "
            "merge through the build journal under that key")
    report = DistReport(workers=workers)
    t0 = time.perf_counter()

    cached = table_cache.load(cache_dir, key)
    if cached is not None:
        table_cache.discard_journal(cache_dir, key)
        report.cache_hit = True
        report.wall_s = time.perf_counter() - t0
        return cached, report
    if not resume:
        table_cache.discard_journal(cache_dir, key)
    journal = table_cache.BuildJournal(cache_dir, key)

    items = latency_work_items(host, method=method, engine=engine)
    report.items = len(items)
    todo = [it for it in items if journal.get(it.key) is None]
    report.journal_prefilled = len(items) - len(todo)

    if todo and workers > 0:
        # Absolute: workers run with cwd=REPO_ROOT, so a relative
        # coordinator path (e.g. CLI --cache-dir cache) would resolve to
        # a DIFFERENT directory there and every worker would die waiting
        # for a manifest.
        wd = os.path.abspath(work_dir
                             or os.path.join(cache_dir, f"dist_{key[:16]}"))
        os.makedirs(wd, exist_ok=True)
        fp_fn = getattr(host, "fingerprint", None)
        manifest = write_manifest(wd, key, todo, engine=engine,
                                  method=method,
                                  host_fp=fp_fn() if fp_fn else None)
        if host_spec is None:
            raise DistBuildError(
                'spawning workers requires host_spec ({"factory": '
                '"module:function", "kwargs": {...}})')
        n = len(manifest["items"])
        store = LeaseStore(wd, "coord", lease_s)
        spawn = lambda w: _spawn_worker(
            wd, w, workers, host_spec, oracle, probe_config,
            engine=engine, method=method, lease_s=lease_s,
            deadline_s=deadline_s, devices=worker_devices,
            platform=worker_platform)
        rcs: dict[int, int] = {}
        deadline = time.monotonic() + deadline_s
        if serial_spawn:
            for w in range(workers):
                if store.count_done(n) == n:
                    break
                rcs[w] = _reap(spawn(w), deadline_s)
        else:
            procs = {w: spawn(w) for w in range(workers)}
            while store.count_done(n) < n:
                if all(p.poll() is not None for p in procs.values()):
                    break
                if time.monotonic() > deadline:
                    for p in procs.values():
                        if p.poll() is None:
                            p.kill()
                    break
                time.sleep(poll_s)
            for w, p in procs.items():
                rcs[w] = _reap(p, grace_s=5.0)
        report.dead_workers = sorted(w for w, rc in rcs.items() if rc != 0)
        if progress:
            progress(f"dist: {store.count_done(n)}/{n} items done by "
                     f"{workers} worker(s); dead={report.dead_workers}")

        # Inline fallback: every worker has exited, so any surviving
        # lease belongs to a dead worker — execute regardless of it.
        cfg = probe_config or probe_engine.ProbeConfig()
        stats = probe_engine.EngineStats(engine=engine)
        coord = ShardJournal(wd, "coord")
        by_key = {it.key: it for it in todo}
        for i, k in enumerate(manifest["items"]):
            if store.is_done(i):
                continue
            holder = store.holder(i)
            if holder and holder != "coord":
                coord.event("steal", item=k, id=i, prev=holder)
            faults.hit("dist.item")
            val, flag = probe_engine.probe_segment(
                host, by_key[k].seg, params, oracle,
                probe_config=cfg, stats=stats)
            coord.put(k, None if val is None else float(val), flag)
            store.mark_done(i)
            report.coordinator_items += 1

        names = [f"w{w}" for w in range(workers)] + ["coord"]
        records, events, corrupt = merge_shards(wd, names)
        report.corrupt_records = corrupt
        # Repair: done-marked items whose shard record was lost or
        # garbled re-execute here — a done marker is a claim, the shard
        # record is the evidence.
        for k in manifest["items"]:
            if k in records:
                continue
            val, flag = probe_engine.probe_segment(
                host, by_key[k].seg, params, oracle,
                probe_config=cfg, stats=stats)
            v = None if val is None else float(val)
            coord.put(k, v, flag)
            records[k] = (v, flag, "coord")
            report.repaired.append(k)
        report.reassigned = sorted(
            {e["item"] for e in events if e.get("evt") == "steal"})
        wins: dict[str, int] = {}
        for _k, (_v, _p, shard_name) in records.items():
            wins[shard_name] = wins.get(shard_name, 0) + 1
        report.completed_by = wins
        journal.put_many(
            [(k,) + records[k][:2] for k in manifest["items"]])
    else:
        wd = None

    tables = build_tables(host, method=method, latency_oracle=oracle,
                          importance=importance, base_perf=base_perf,
                          params=params, progress=progress, prune=prune,
                          engine=engine, cache_dir=cache_dir,
                          probe_config=probe_config, resume=True)
    if wd is not None and not keep_work_dir:
        shutil.rmtree(wd, ignore_errors=True)
    report.wall_s = time.perf_counter() - t0
    return tables, report
