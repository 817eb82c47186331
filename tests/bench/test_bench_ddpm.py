"""The DDPM family (``bench/ddpm.py``) at a toy size, on the CPU.

The plain reference (``bench/ddpm_reference.py``) imports nothing of the
program; here it is held to the program's original and replaced network
and to the merged sampler, and the family is driven through the harness
on a tiny cell added the way a later change adds one: new files and new
entries in ``BENCHMARK.json``.  The family's two per-layer readers are
checked on planted role times.  No number these runs print is a device
measurement.
"""
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import ddpm, ddpm_reference as R, harness, scopes, traces

from conftest import CPU, DATA, ROOT, make_root

CELL = "tiny_ddpm.steps3"


def _load(plan="tiny_ddpm.plan.json"):
    with open(os.path.join(DATA, "tiny_ddpm.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, plan)) as f:
        cfg["plan_text"] = f.read()
    cfg["plan_json"] = json.loads(cfg["plan_text"])
    return cfg


def _rel(y, ref):
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(y - ref).max() / np.abs(ref).max())


@pytest.fixture(scope="module")
def tiny():
    cfg = _load()
    params = ddpm.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 16, 16, 3))
    return cfg, params, x


def test_original_network_matches_reference(tiny):
    """Under the identity plan the reference is the published network,
    and the program's original network equals it."""
    from repro.core.plan import identity_plan
    from repro.models import cnn
    cfg, params, x = tiny
    net = ddpm._zoo_net(cfg)
    ident = identity_plan(net.L, net.layer_descs())
    t = jnp.array([0, 500, 999])
    ref = R.forward(cfg, params, x, t, json.loads(ident.to_json()))
    assert _rel(cnn.apply_replaced(net, params, (x, t)), ref) < 1e-5


def test_published_widths_have_the_published_parameter_count():
    """The configuration's network, in the program and in the reference,
    holds the 35,746,307 parameters of ``diffusion_tf``'s CIFAR-10 UNet
    (the paper's 35.7M): counted from shapes, nothing allocated."""
    from repro.models import cnn
    with open(os.path.join(ROOT, "bench", "configs",
                           "ddpm_cifar10.json")) as f:
        cfg = json.load(f)

    def count(init, *args):
        shapes = jax.eval_shape(lambda k: init(*args, k),
                                jax.random.PRNGKey(0))
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count(R.init_params, cfg) == 35_746_307
    assert count(cnn.init_params, ddpm._zoo_net(cfg)) == 35_746_307


def test_replaced_network_matches_reference(tiny):
    """The stored plan merges conv1∘conv2 in three ResBlocks and prunes a
    conv in three: the program's replaced network equals the reference's,
    and so does the merged executor."""
    from repro import runtime
    from repro.core.plan import CompressionPlan
    from repro.models import cnn, cnn_host
    cfg, params, x = tiny
    pj = cfg["plan_json"]
    assert sum(len(s["kept"]) == 2 for s in pj["segments"]) == 3
    assert sum(s["j"] - s["i"] == 2 and len(s["kept"]) == 1
               for s in pj["segments"]) == 3
    net = ddpm._zoo_net(cfg)
    plan = CompressionPlan.from_json(cfg["plan_text"])
    t = jnp.array([7, 250, 950])
    ref = R.forward(cfg, params, x, t, pj)
    assert _rel(cnn.apply_replaced(net, params, (x, t), plan), ref) < 1e-5
    graph = cnn_host.CNNHost(net, params).lower_plan(plan)
    assert _rel(runtime.execute(graph, (x, t)), ref) < 1e-5


def test_sampler_matches_reference(tiny, tmp_path):
    """Three DDIM steps of the merged program (built as the harness builds
    it: plan → artifact → sampler) equal the reference's three steps, and
    compile once."""
    cfg, params, x = tiny
    smp = ddpm.build(cfg, params, cfg["plan_text"], str(tmp_path),
                     traffic={"steps": 3})
    y = smp.apply(x)
    smp.apply(x)
    assert dict(smp.executor.traces) == {("sample", x.shape): 1}
    assert smp.steps_run == 6
    ref = R.sample(cfg, params, x, cfg["plan_json"], 3)
    assert _rel(y, ref) < cfg["check"]["max_rel_err"] / 4
    ctl = R.sample(cfg, params, x, cfg["plan_json"], 3, passes=3)
    assert _rel(ctl, ref) > cfg["check"]["max_rel_err"]


def test_family_contract(tiny):
    cfg, _, _ = tiny
    assert cfg["work"] == ddpm.work(cfg, cfg["plan_json"])
    ddpm.check_config(cfg)
    assert ddpm.forwards(cfg, {"steps": 3}) == 3
    units = cfg["work"]["units"]
    assert units[0]["scope"] == "temb" and units[0]["kernel"] is None
    attn = [u for u in units if u["kind"] == "attention"]
    assert len(attn) == 4 and all(u["kernel"] is None for u in attn)
    kernels = [u["kernel"] for u in units if u["kernel"]]
    assert set(kernels) == {"merged_conv"}
    bad = json.loads(cfg["plan_text"])
    bad["segments"][1:3] = [{"i": 1, "j": 4, "k": 5, "kept": [2, 3],
                             "original": False}]
    with pytest.raises(ValueError):
        ddpm.check_config({**cfg, "plan_text": json.dumps(bad)})


def _root(dst):
    """A checkout root holding the benchmark plus the tiny DDPM cell."""
    make_root(dst, configs=())
    bench = os.path.join(dst, "bench")
    for name in ("tiny_ddpm.json", "tiny_ddpm.plan.json"):
        shutil.copy(os.path.join(DATA, name), os.path.join(bench, "configs"))
    shutil.copy(os.path.join(DATA, "tiny_steps3.json"),
                os.path.join(bench, "workloads"))
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny_ddpm", "source": "test",
                          "file": "bench/configs/tiny_ddpm.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tiny_ddpm",
                            "traffic": "tiny_steps3", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("images_per_s", "mfu", "ddpm.attention_share",
                         "ddpm.between_forwards_share"):
            m["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return str(dst)


def _run(root, trace=False, seed=2**33 + 11):
    return harness.run(harness.Registry(root), CELL, seed, 0.2, trace,
                       time.perf_counter(), dict(CPU))


def test_cell_runs_correct_through_the_harness(tmp_path):
    r = _run(_root(tmp_path))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    chk = r["checks"]["max_rel_err"]
    assert chk["value"] < chk["limit"]


def test_traced_run_counts_three_forwards(tmp_path, monkeypatch):
    """The traced run hands the sampler's steps to the readers; a CPU
    trace has no TPU plane, so the family's device-time readers are left
    out, never reported 0."""
    from bench import readers
    root = _root(tmp_path)
    seen = []

    def mfu(ctx):
        seen.append(ctx.forwards)
        return 1.0
    monkeypatch.setattr(readers, "mfu", mfu)
    path = os.path.join(root, "bench", "peaks.json")
    with open(path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    with open(path, "w") as f:
        json.dump(peaks, f)
    r = _run(root, trace=True)
    assert r["correct"] is True and seen == [3]
    assert set(r["metrics"]) == {"mfu"}


def test_a_step_left_out_is_not_correct(tmp_path):
    build = ddpm.build

    def two_steps(cfg, params, plan_text, workdir, clock=None, *, traffic):
        return build(cfg, params, plan_text, workdir, clock,
                     traffic={**traffic, "steps": 2})
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ddpm, "build", two_steps)
        r = _run(_root(tmp_path))
    assert r["correct"] is False and r["failed"] >= 1


def _ctx(by_unit, op_s, units, loops=()):
    """A context whose trace lists ``loops`` (name, s) among its largest
    ops and, as they are unscoped, among the largest ``other`` ops."""
    roles = scopes.Roles(op_s=op_s, by_role={r: sum(u.values())
                                             for r, u in by_unit.items()},
                         by_unit=by_unit, other_ops=[list(o) for o in loops])
    trace = traces.Reduced(window_s=1.0, busy_s=1.0, op_s=op_s, kernel_s={},
                           top_ops=[list(o) for o in loops] + [
                               ["merged_conv:merged_conv.3", 0.5]],
                           idle_gaps=[], kernel_order=[])
    return harness.MetricContext(trace=trace, work={"units": units},
                                 peak={}, batch=1, calls=1,
                                 images_per_s=0.0, chips=1, roles=roles)


UNITS = [{"unit": "temb", "scope": "temb", "kind": "temb", "kernel": None},
         {"unit": "conv0_1", "scope": "unit00", "kind": "conv",
          "kernel": "merged_conv"},
         {"unit": "attn1_2", "scope": "unit01", "kind": "attention",
          "kernel": None},
         {"unit": "conv2_3", "scope": "unit02", "kind": "conv",
          "kernel": "merged_conv"},
         {"unit": "attn3_4", "scope": "unit03", "kind": "attention",
          "kernel": None}]


def test_readers_on_planted_roles():
    """Attention: every role of the attention units' scopes; between
    forwards: ``other`` time outside any unit (the time MLP, the DDIM
    update), not ``head``, not a unit's own ``other``."""
    by_unit = {"kernel": {"unit00": 4.0, "unit02": 2.0},
               "epilogue": {"unit01": 1.5, "unit03": 0.5, "unit00": 0.25},
               "mixed": {"unit03": 0.25},
               "other": {"": 1.0, "unit02": 0.25},
               "head": {"head": 0.25}}
    ctx = _ctx(by_unit, 10.0, UNITS)
    assert ddpm.attention_share(ctx) == pytest.approx(22.5)
    assert ddpm.between_forwards_share(ctx) == pytest.approx(10.0)


def test_readers_leave_the_step_loop_out():
    """The sampler's ``while`` event spans the call, over the ops of its
    body the trace also lists: it counts on neither side of a share."""
    by_unit = {"kernel": {"unit00": 4.0, "unit02": 2.0},
               "epilogue": {"unit01": 1.5, "unit03": 0.5},
               "other": {"": 9.5 + 0.5}}
    ctx = _ctx(by_unit, 18.0, UNITS, loops=[("while.19", 9.5),
                                           ("while", 0.5)])
    assert ddpm.attention_share(ctx) == pytest.approx(25.0)
    assert ddpm.between_forwards_share(ctx) == pytest.approx(0.0)


def test_readers_find_nothing_to_read():
    empty = _ctx({}, 0.0, UNITS)
    assert ddpm.attention_share(empty) is None
    assert ddpm.between_forwards_share(empty) is None
    cnn_work = [{"unit": "conv0_1", "kernel": "merged_conv"}]
    busy = _ctx({"other": {"": 1.0}}, 2.0, cnn_work)
    assert ddpm.attention_share(busy) is None
    assert ddpm.between_forwards_share(busy) is None
    no_attn = _ctx({"other": {"": 1.0}}, 2.0, UNITS[:2])
    assert ddpm.attention_share(no_attn) is None
    assert ddpm.between_forwards_share(no_attn) == pytest.approx(50.0)
