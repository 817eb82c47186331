"""Distributed table-build certification (ISSUE 8 acceptance bars).

* Lease protocol: atomic claims, renewal, expiry-driven stealing with
  epoch bumps and read-back verification, done markers.
* Merge: deterministic first-wins shard merge, corrupt-record counting,
  repair of done-marked items whose shard evidence is missing.
* Bit-identity: 2- and 4-worker subprocess builds produce tables
  bit-identical to the sequential single-process reference — including
  a worker SIGKILLed mid-bucket whose lease is reassigned.
* Publish gating: a non-zero process index writes NO artifact, cache,
  journal, or bench file (the at-most-once publish contract).
"""
import json
import os
import time

import pytest

from repro.core import build_tables, dist_build_tables, table_cache
from repro.core.dist_build import (DistBuildError, LeaseStore, ShardJournal,
                                   latency_work_items, merge_shards,
                                   resolve_host_spec, write_manifest)
from repro.launch import distributed as dist
from repro.testing import faults
from repro.testing.hosts import tiny_resnet_host

HOST_SPEC = {"factory": "repro.testing.hosts:tiny_resnet_host",
             "kwargs": {}}


@pytest.fixture(scope="module")
def smoke_host():
    return tiny_resnet_host()


@pytest.fixture(scope="module")
def reference(smoke_host):
    host, params = smoke_host
    return build_tables(host, params=params)


# ---------------------------------------------------------------------------
# Lease protocol
# ---------------------------------------------------------------------------

def test_lease_claim_renew_release(tmp_path):
    a = LeaseStore(str(tmp_path), "w0", lease_s=30.0)
    b = LeaseStore(str(tmp_path), "w1", lease_s=30.0)
    got, stolen = a.claim(0)
    assert got and stolen is None
    # a live foreign lease cannot be claimed
    assert b.claim(0) == (False, None)
    # re-claiming our own lease renews it
    assert a.claim(0) == (True, None)
    assert a.renew(0)
    assert b.holder(0) == "w0"
    # release is owner-only
    b.release(0)
    assert a.holder(0) == "w0"
    a.release(0)
    assert a.holder(0) is None
    assert b.claim(0) == (True, None)


def test_lease_expiry_steal_and_epoch(tmp_path):
    a = LeaseStore(str(tmp_path), "w0", lease_s=0.05)
    b = LeaseStore(str(tmp_path), "w1", lease_s=30.0)
    assert a.claim(3) == (True, None)
    time.sleep(0.1)                          # w0's lease expires
    got, stolen = b.claim(3)
    assert got and stolen == "w0"
    rec = json.load(open(os.path.join(str(tmp_path), "leases", "3.json")))
    assert rec["owner"] == "w1" and rec["epoch"] == 2
    # the loser notices the steal on renew
    assert not a.renew(3)


def test_done_markers(tmp_path):
    s = LeaseStore(str(tmp_path), "w0", lease_s=30.0)
    assert not s.is_done(1)
    s.mark_done(1)
    assert s.is_done(1)
    assert s.count_done(3) == 1


# ---------------------------------------------------------------------------
# Shards and merge
# ---------------------------------------------------------------------------

def test_merge_shards_first_wins_and_corrupt(tmp_path):
    wd = str(tmp_path)
    w0 = ShardJournal(wd, "w0")
    w1 = ShardJournal(wd, "w1")
    w0.put("a", 1.0, "measured")
    w1.put("a", 2.0, "measured")             # duplicate: w0 wins
    w1.put("b", 3.0, "quarantined")
    w1.event("steal", item="b", id=1, prev="w0")
    with open(os.path.join(wd, "shards", "w1.jsonl"), "ab") as f:
        f.write(b"#garbled journal record#\n")
    records, events, corrupt = merge_shards(wd, ["w0", "w1"])
    assert records["a"] == (1.0, "measured", "w0")
    assert records["b"] == (3.0, "quarantined", "w1")
    assert corrupt == 1
    assert events == [{"evt": "steal", "item": "b", "id": 1, "prev": "w0",
                       "shard": "w1"}]
    # reversed order flips the winner: the order IS the determinism
    rev, _, _ = merge_shards(wd, ["w1", "w0"])
    assert rev["a"] == (2.0, "measured", "w1")


def test_manifest_idempotent_and_drift_loud(tmp_path, smoke_host):
    host, _params = smoke_host
    items = latency_work_items(host)
    wd = str(tmp_path)
    m1 = write_manifest(wd, "k1", items, engine="batched",
                        method="layermerge", host_fp="fp")
    m2 = write_manifest(wd, "k1", items, engine="batched",
                        method="layermerge", host_fp="fp")
    assert m1 == m2
    with pytest.raises(DistBuildError, match="different build"):
        write_manifest(wd, "k2", items, engine="batched",
                       method="layermerge", host_fp="fp")


def test_host_spec_roundtrip_same_fingerprint(smoke_host):
    host, _params = smoke_host
    rebuilt, _p = resolve_host_spec(HOST_SPEC)
    assert rebuilt.fingerprint() == host.fingerprint()
    with pytest.raises(DistBuildError, match="module:function"):
        resolve_host_spec({"factory": "nonsense"})


def test_worker_env_spec_translation():
    with faults.inject(
            faults.Fault("dist.item", "kill-worker", nth=2, widx=0),
            faults.Fault("dist.claim", "stall-worker", seconds=0.5,
                         widx=1),
            faults.Fault("", "corrupt-shard", widx=1)):
        assert faults.worker_env_spec(0) == "exit@dist.item:2x1"
        assert faults.worker_env_spec(1) == \
            "delay@dist.claim:1x1~0.5;garble@dist.shard.append:1x1"
        assert faults.worker_env_spec(2) is None
        # worker-targeted rules NEVER fire in the planning process
        faults.hit("dist.item")
        faults.hit("dist.item")
    assert faults.worker_env_spec(0) is None  # no active plan


# ---------------------------------------------------------------------------
# Bit-identity: subprocess fan-out vs sequential reference
# ---------------------------------------------------------------------------

def _dist(host, params, cache_dir, workers, **kw):
    return dist_build_tables(host, params=params, cache_dir=str(cache_dir),
                             workers=workers, host_spec=HOST_SPEC, **kw)


@pytest.mark.parametrize("workers", [2, 4])
def test_clean_fanout_bit_identical(smoke_host, reference, tmp_path,
                                    workers):
    host, params = smoke_host
    tables, rep = _dist(host, params, tmp_path, workers, lease_s=10.0)
    assert tables.entries == reference.entries
    assert tables.num_pruned == reference.num_pruned
    assert tables.provenance == reference.provenance
    assert rep.dead_workers == []
    assert not rep.cache_hit
    assert sum(rep.completed_by.values()) == rep.items
    # the published cache now serves a hit
    _t2, rep2 = _dist(host, params, tmp_path, workers)
    assert rep2.cache_hit


def test_sigkilled_worker_lease_reassigned(smoke_host, reference,
                                           tmp_path):
    """ISSUE acceptance: worker 0 dies mid-bucket (holding a lease, no
    result); worker 1 steals the expired lease, and the merged tables
    are bit-identical to the sequential build."""
    host, params = smoke_host
    with faults.inject(faults.Fault("dist.item", "kill-worker", nth=2,
                                    widx=0)):
        tables, rep = _dist(host, params, tmp_path, 2, lease_s=0.5,
                            serial_spawn=True)
    assert 0 in rep.dead_workers
    assert rep.reassigned, "the killed worker's lease was never stolen"
    assert tables.entries == reference.entries
    assert tables.num_pruned == reference.num_pruned
    assert tables.provenance == reference.provenance


def test_corrupt_shard_records_repaired(smoke_host, reference, tmp_path):
    """Garbled shard lines are counted, never trusted: the coordinator
    re-executes those items (repair) and the tables stay bit-identical."""
    host, params = smoke_host
    # both workers garble their first records: either may claim every
    # item before the other starts
    with faults.inject(*(faults.Fault("", "corrupt-shard", nth=1, times=2,
                                      widx=w) for w in range(2))):
        tables, rep = _dist(host, params, tmp_path, 2, lease_s=10.0)
    assert rep.corrupt_records >= 1
    assert rep.repaired, "garbled records were not re-executed"
    assert tables.entries == reference.entries
    assert tables.provenance == reference.provenance


def test_relative_work_dir_from_foreign_cwd(smoke_host, reference,
                                            tmp_path, monkeypatch):
    """Workers run with cwd=REPO_ROOT; a RELATIVE coordinator cache/work
    dir must still reach them (regression: every worker died waiting for
    a manifest that lived under the coordinator's cwd), and each worker
    leaves a log file for post-mortems."""
    from repro.core.dist_build import worker_log_path

    host, params = smoke_host
    monkeypatch.chdir(tmp_path)
    tables, rep = _dist(host, params, "cache", 2, work_dir="wd",
                        keep_work_dir=True, lease_s=10.0)
    assert tables.entries == reference.entries
    assert rep.dead_workers == []
    assert sum(rep.completed_by.values()) == rep.items
    assert rep.coordinator_items == 0
    for w in range(2):
        assert os.path.exists(worker_log_path(str(tmp_path / "wd"), w))


def test_workers_zero_degenerates_to_local(smoke_host, reference,
                                           tmp_path):
    host, params = smoke_host
    tables, rep = dist_build_tables(host, params=params,
                                    cache_dir=str(tmp_path), workers=0)
    assert tables.entries == reference.entries
    assert rep.coordinator_items == 0 and rep.completed_by == {}


def test_uncacheable_build_is_loud(tmp_path):
    class NoFingerprint:
        pass

    with pytest.raises(DistBuildError, match="content-addressable"):
        dist_build_tables(NoFingerprint(), cache_dir=str(tmp_path),
                          workers=2)


def test_wallclock_fanout_refused_off_cpu(smoke_host, tmp_path,
                                          monkeypatch):
    """A parent that holds a chip cannot hand wall-clock probes to CPU
    workers: the build refuses before it spawns anything."""
    import jax

    from repro.core import WallClockOracle

    host, params = smoke_host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(DistBuildError, match="workers would time the CPU"):
        dist_build_tables(host, params=params, cache_dir=str(tmp_path),
                          workers=2, latency_oracle=WallClockOracle())
    assert os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# Publish gating: a non-main process writes NOTHING
# ---------------------------------------------------------------------------

def test_non_main_process_writes_nothing(smoke_host, reference, tmp_path,
                                         monkeypatch):
    """With a non-zero process index every publish path — table cache,
    build journal, artifact, gated text/JSON — is inert on disk while
    still returning its in-memory result."""
    from repro import runtime
    from repro.core.plan import identity_plan

    host, params = smoke_host
    graph = host.lower_plan(
        identity_plan(host.net.L, host.net.layer_descs(params)))
    main_fp = runtime.save(str(tmp_path / "main.npz"), graph)

    monkeypatch.setenv(dist.ENV_PROCESS_ID, "1")
    monkeypatch.setenv(dist.ENV_NUM_PROCESSES, "2")
    assert dist.process_index() == 1 and not dist.is_main()

    d = tmp_path / "nonmain"
    # table cache publish: path returned, file absent
    path = table_cache.save(str(d), "k" * 8, reference)
    assert not os.path.exists(path)
    # build journal: in-memory only
    j = table_cache.BuildJournal(str(d), "k" * 8)
    j.put("lat:0:1:1", 1.0)
    assert j.put_many([("a", 1.0, "measured")]) == 1
    assert j.get("a") == (1.0, "measured")
    assert not os.path.exists(j.path)
    # artifact: fingerprint computed (and equal to main's), file absent
    fp = runtime.save(str(d / "m.npz"), graph)
    assert fp == main_fp and not os.path.exists(str(d / "m.npz"))
    # gated text/JSON publishes
    assert dist.publish_text(str(d / "t.txt"), "x") is None
    assert dist.publish_json(str(d / "b.json"), {"x": 1}) is None
    assert not os.path.exists(str(d))

    monkeypatch.setenv(dist.ENV_PROCESS_ID, "0")
    assert dist.is_main()
    assert dist.publish_json(str(d / "b.json"), {"x": 1}) is not None
    assert json.load(open(d / "b.json")) == {"x": 1}
