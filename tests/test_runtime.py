"""repro.runtime certification: one shared executor + portable artifacts.

* executor-vs-legacy equivalence (allclose) on every zoo CNN host and on
  transformer hosts across sublayer families, under both ``replaced``
  (unmerged) and ``merged`` modes;
* artifact save → load → re-execute round trips with fingerprint
  stability, including a fresh-process reload (bit-identical plan,
  equivalent outputs);
* corrupt / torn / stale artifacts are rejected (the table-cache torn-
  file contract, but *loud*: deployment must never run a bit-rotted
  model silently);
* the ``python -m repro.compress`` CLI produces a loadable artifact.
"""
import dataclasses
import json
import os
import subprocess
import sys

from repro.testing.subproc import subprocess_env

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.configs import get_config
from repro.core import compress
from repro.core.plan import identity_plan
from repro.models import cnn, cnn_host, zoo
from repro.models import transformer as T
from repro.models.transformer_host import CostEnv, TransformerHost

_SUBPROC_ENV = subprocess_env()

CNN_ZOO = {
    "tiny_resnet": lambda: zoo.tiny_resnet(num_classes=4, in_hw=8, width=4,
                                           blocks=(2,)),
    "tiny_resnet_gn": lambda: zoo.tiny_resnet(num_classes=4, in_hw=8,
                                              width=16, blocks=(2,),
                                              norm="gn"),
    "tiny_mobilenet": lambda: zoo.tiny_mobilenet(num_classes=4, in_hw=8,
                                                 width=8),
    "tiny_unet": lambda: zoo.ddpm_unet(in_hw=8, ch=8, ch_mult=(1, 2),
                                       num_res_blocks=1,
                                       attn_resolutions=(4,), gn_groups=4),
}

TRANSFORMER_ARCHS = ("smollm-135m", "granite-moe-1b-a400m",
                     "recurrentgemma-2b")


def _cnn_setup(name):
    net = CNN_ZOO[name]()
    params = cnn.init_params(net, jax.random.PRNGKey(0))
    host = cnn_host.CNNHost(net, params, batch=2)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, net.in_hw, net.in_hw, net.in_ch))
    if net.temb_ch:                  # a time-conditioned net takes (x, t)
        x = (x, jnp.array([3, 700]))
    return net, params, host, x


def _tf_setup(arch, num_layers=None):
    cfg = get_config(arch).reduced()
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    host = TransformerHost(cfg, params, env=CostEnv(batch=2, seq=16))
    B, S = 2, 16
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size),
             "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
    return cfg, params, host, batch


def _allclose(a, b, rtol=1e-4):
    scale = float(jnp.abs(a).max()) + 1e-9
    assert float(jnp.abs(a - b).max()) / scale < rtol, \
        float(jnp.abs(a - b).max())


# ---------------------------------------------------------------------------
# Executor vs legacy forward paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CNN_ZOO))
def test_cnn_executor_matches_legacy(name):
    """Merged executor ≈ legacy replaced forward (the paper's exactness)
    on compressed plans, and ≡ it on the identity plan."""
    net, params, host, x = _cnn_setup(name)
    tested = 0
    for ratio in (0.6, 0.8):
        res = compress(host, budget_ratio=ratio, P=100)
        if res is None:
            continue
        y_legacy = cnn.apply_replaced(net, params, x, res.plan)
        y_exec = runtime.execute(host.lower_plan(res.plan), x)
        _allclose(y_legacy, y_exec)
        ma, _ = host.merged_apply(res.plan)
        np.testing.assert_array_equal(np.asarray(ma(params, x)),
                                      np.asarray(y_exec))
        tested += 1
    assert tested > 0
    ident = identity_plan(net.L, net.layer_descs())
    y0 = cnn.apply_replaced(net, params, x)
    y0_exec = runtime.execute(host.lower_plan(ident), x)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y0_exec))


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_transformer_executor_matches_legacy(arch):
    """Executor (replaced + merged graphs) ≈ the legacy tuple-unit
    ``T.forward_compressed`` path, across attn/ffn/moe/rglru sublayers."""
    cfg, params, host, batch = _tf_setup(arch)
    tested = 0
    for ratio in (0.6, 0.8):
        res = compress(host, budget_ratio=ratio, P=100)
        if res is None:
            continue
        for merged in (False, True):
            graph = host.lower_plan(res.plan, merged=merged)
            legacy_units = [
                ("merged", (u.params["u"], u.params["v"]))
                if u.kind == "lowrank" else
                ("orig", {"norm": u.params["norm"], "p": u.params["p"],
                          "kind": u.sub_kind})
                for u in graph.units]
            y_legacy = T.forward_compressed(cfg, params, legacy_units, batch)
            y_exec = runtime.execute(graph, batch)
            _allclose(y_legacy, y_exec)
        ra, _ = host.replaced_apply(res.plan)
        ma, _ = host.merged_apply(res.plan)
        _allclose(ra(params, batch), ma(params, batch))
        tested += 1
    assert tested > 0


def test_jit_apply_params_pytree():
    """jit_apply exposes the graph's arrays as a pytree argument; scaling
    the head through the pytree must change the output (no stale
    closure-captured constants)."""
    net, params, host, x = _cnn_setup("tiny_resnet")
    res = compress(host, budget_ratio=0.7, P=100)
    graph = host.lower_plan(res.plan)
    fn, gp = runtime.jit_apply(graph)
    y = fn(gp, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(runtime.execute(graph, x)),
                               rtol=1e-6, atol=1e-6)
    gp2 = jax.tree.map(lambda a: a, gp)
    gp2["globals"]["head"]["w"] = gp["globals"]["head"]["w"] * 2.0
    assert float(jnp.abs(fn(gp2, x) - y).max()) > 0


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

def test_decode_matches_prefill():
    """Token-by-token decode through the compressed graph reproduces the
    parallel prefill logits at the last position (KV-cache correctness)."""
    cfg, params, host, batch = _tf_setup("smollm-135m", num_layers=4)
    res = compress(host, budget_ratio=0.6, P=200)
    graph = host.lower_plan(res.plan)
    y = runtime.execute(graph, batch)
    B, S = batch["tokens"].shape
    cache = runtime.init_cache(graph, B, S)
    step, gp = runtime.make_serve_step(graph)
    step = jax.jit(step)
    logits = None
    for t in range(S):
        logits, cache = step(gp, cache,
                             {"tokens": batch["tokens"][:, t:t + 1]})
    _allclose(y[:, -1], logits[:, 0], rtol=2e-4)


# ---------------------------------------------------------------------------
# Artifact round trips
# ---------------------------------------------------------------------------

def _save_cnn_artifact(tmp_path, name="tiny_resnet", ratio=0.7):
    net, params, host, x = _cnn_setup(name)
    res = compress(host, budget_ratio=ratio, P=100)
    path = os.path.join(tmp_path, f"{name}.npz")
    fp = res.save(path)
    return res, host, x, path, fp


def test_artifact_roundtrip_cnn(tmp_path):
    res, host, x, path, fp = _save_cnn_artifact(str(tmp_path))
    art = runtime.load(path)
    assert art.fingerprint == fp
    assert art.plan == res.plan                       # bit-identical plan
    assert art.meta["oracle"] and "AnalyticTPUOracle" in art.meta["oracle"]
    y_live = runtime.execute(host.lower_plan(res.plan), x)
    np.testing.assert_array_equal(np.asarray(y_live),
                                  np.asarray(art.apply(x)))


def test_artifact_roundtrip_transformer(tmp_path):
    cfg, params, host, batch = _tf_setup("smollm-135m", num_layers=4)
    res = compress(host, budget_ratio=0.6, P=200)
    path = os.path.join(str(tmp_path), "lm.npz")
    res.save(path)
    art = runtime.load(path)
    assert art.plan == res.plan
    assert art.graph.meta["config"] == cfg            # ArchConfig round-trip
    y_live = runtime.execute(host.lower_plan(res.plan), batch)
    np.testing.assert_array_equal(np.asarray(y_live),
                                  np.asarray(art.apply(batch)))


def test_artifact_fingerprint_stable(tmp_path):
    """Same graph + plan + meta ⇒ same fingerprint, across saves and
    across a load→save round trip (content addressing, not timestamps)."""
    res, host, x, path, fp1 = _save_cnn_artifact(str(tmp_path))
    fp2 = res.save(os.path.join(str(tmp_path), "again.npz"))
    assert fp1 == fp2
    art = runtime.load(path)
    fp3 = runtime.save(os.path.join(str(tmp_path), "resaved.npz"),
                       art.graph, plan=art.plan, meta=art.meta)
    assert fp3 == fp1
    # different weights ⇒ different fingerprint
    net = CNN_ZOO["tiny_resnet"]()
    params2 = cnn.init_params(net, jax.random.PRNGKey(7))
    host2 = cnn_host.CNNHost(net, params2, batch=2)
    fp4 = runtime.fingerprint(host2.lower_plan(res.plan), res.plan,
                              art.meta)
    assert fp4 != fp1


def test_artifact_fresh_process_reload(tmp_path):
    """An artifact written here reloads in a FRESH process to a
    bit-identical plan and equivalent outputs."""
    res, host, x, path, fp = _save_cnn_artifact(str(tmp_path))
    y_live = np.asarray(runtime.execute(host.lower_plan(res.plan), x))
    xpath = os.path.join(str(tmp_path), "x.npy")
    np.save(xpath, np.asarray(x))
    code = (
        "import sys, json, numpy as np\n"
        "from repro import runtime\n"
        "art = runtime.load(sys.argv[1])\n"
        "y = np.asarray(art.apply(np.load(sys.argv[2])))\n"
        "np.save(sys.argv[3], y)\n"
        "print('PLAN=' + art.plan.to_json().replace(chr(10), ''))\n"
        "print('FP=' + art.fingerprint)\n"
    )
    ypath = os.path.join(str(tmp_path), "y.npy")
    r = subprocess.run([sys.executable, "-c", code, path, xpath, ypath],
                       capture_output=True, text=True, env=_SUBPROC_ENV,
                       cwd="/root/repo", timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"FP={fp}" in r.stdout
    plan_line = [l for l in r.stdout.splitlines()
                 if l.startswith("PLAN=")][0]
    from repro.core.plan import CompressionPlan
    assert CompressionPlan.from_json(plan_line[5:]) == res.plan
    np.testing.assert_allclose(np.load(ypath), y_live, rtol=1e-5,
                               atol=1e-6)


def test_artifact_finetune_consumer(tmp_path):
    """A reloaded artifact is trainable: ``make_train_step`` over the
    graph's params pytree takes finite, loss-reducing AdamW steps —
    compression runs once and fine-tuning resumes from the same object
    serving uses."""
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.train.step import make_compressed_forward, make_train_step

    cfg, params, host, batch = _tf_setup("smollm-135m", num_layers=4)
    res = compress(host, budget_ratio=0.6, P=200)
    path = os.path.join(str(tmp_path), "lm.npz")
    res.save(path)
    art = runtime.load(path)
    gp = runtime.graph_params(art.graph)
    step = jax.jit(make_train_step(
        cfg, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10),
        forward_fn=make_compressed_forward(art.graph)))
    tbatch = dict(batch)
    tbatch["targets"] = jnp.roll(batch["tokens"], -1, axis=1)
    opt = init_opt_state(gp)
    losses = []
    for _ in range(5):
        gp, opt, metrics = step(gp, opt, tbatch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# Corrupt / torn / stale artifacts are rejected
# ---------------------------------------------------------------------------

def test_artifact_missing_is_error(tmp_path):
    with pytest.raises(runtime.ArtifactError):
        runtime.load(os.path.join(str(tmp_path), "nope.npz"))


def test_artifact_torn_write_rejected(tmp_path):
    """A truncated file (crash mid-write without the atomic rename) must
    raise, mirroring test_probe_engine's torn-cache case."""
    _, _, _, path, _ = _save_cnn_artifact(str(tmp_path))
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[: len(raw) // 3])
    with pytest.raises(runtime.ArtifactError):
        runtime.load(path)
    assert not os.path.exists(path + ".tmp")    # atomic publish leaves none


def test_artifact_bitrot_rejected(tmp_path):
    """A structurally-valid npz whose weights were tampered with must
    fail fingerprint verification."""
    _, _, _, path, _ = _save_cnn_artifact(str(tmp_path))
    with np.load(path, allow_pickle=False) as z:
        data = {k: z[k] for k in z.files}
    wkey = sorted(k for k in data if k.endswith("/w"))[0]
    data[wkey] = data[wkey] + 1.0
    with open(path, "wb") as f:
        np.savez(f, **data)
    with pytest.raises(runtime.ArtifactError, match="fingerprint"):
        runtime.load(path)


def test_artifact_stale_format_rejected(tmp_path):
    _, _, _, path, _ = _save_cnn_artifact(str(tmp_path))
    with np.load(path, allow_pickle=False) as z:
        data = {k: z[k] for k in z.files}
    spec = json.loads(data["__spec__"].item())
    spec["format"] = 99
    data["__spec__"] = np.array(json.dumps(spec))
    with open(path, "wb") as f:
        np.savez(f, **data)
    with pytest.raises(runtime.ArtifactError, match="format"):
        runtime.load(path)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_mobilenet_e2e_pallas_certified(tmp_path):
    """MobileNetV2-family end-to-end: compress → every conv unit of the
    lowered graph (pointwise, depthwise, strided, merged-fat) certified on
    the Pallas kernels in interpret mode against the ref oracles → artifact
    → fresh-process reload exactness.

    Uses ``tiny_mobilenet`` — the same inverted-residual generator as the
    ``mobilenetv2`` zoo config (expand 1×1 / depthwise 3×3 / project 1×1,
    strided blocks included) at CI scale."""
    net, params, host, x = _cnn_setup("tiny_mobilenet")
    res = compress(host, budget_ratio=0.7, P=100)
    assert res is not None
    graph = host.lower_plan(res.plan)
    conv_units = [u for u in graph.units if u.kind == "conv"]
    dw_units = [u for u in conv_units if u.depthwise]
    assert dw_units, "plan kept no depthwise unit — not exercising the path"
    # every conv unit runs its deployment kernel (interpret on CPU) and
    # matches the jnp oracle at the unit's real weights and geometry
    from repro import kernels
    rng = np.random.default_rng(0)
    with kernels.force_backend("pallas"):
        for u in conv_units:
            w, b = u.params["w"], u.params["b"]
            K = w.shape[0]
            cin = w.shape[3] if u.depthwise else w.shape[2]
            hw = K + 3 * u.stride
            xin = jnp.asarray(rng.standard_normal((1, hw, hw, cin)),
                              jnp.float32)
            if u.depthwise:
                y = kernels.depthwise_conv_op(xin, w, b, stride=u.stride,
                                              interpret=True)
                yr = kernels.depthwise_conv_ref(xin, w, b, stride=u.stride)
            else:
                y = kernels.merged_conv_op(xin, w, b, stride=u.stride,
                                           interpret=True)
                yr = kernels.merged_conv_ref(xin, w, b, stride=u.stride)
            np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                       rtol=2e-5, atol=2e-5)
    # artifact round trip: fresh process, bit-identical plan, equal outputs
    path = os.path.join(str(tmp_path), "mnv2.npz")
    fp = res.save(path)
    y_live = np.asarray(runtime.execute(graph, x))
    xpath = os.path.join(str(tmp_path), "x.npy")
    np.save(xpath, np.asarray(x))
    code = (
        "import sys, numpy as np\n"
        "from repro import runtime\n"
        "art = runtime.load(sys.argv[1])\n"
        "np.save(sys.argv[3], np.asarray(art.apply(np.load(sys.argv[2]))))\n"
        "print('FP=' + art.fingerprint)\n"
        "print('DW=%d' % sum(1 for u in art.graph.units\n"
        "                    if u.kind == 'conv' and u.depthwise))\n"
    )
    ypath = os.path.join(str(tmp_path), "y.npy")
    r = subprocess.run([sys.executable, "-c", code, path, xpath, ypath],
                       capture_output=True, text=True, env=_SUBPROC_ENV,
                       cwd="/root/repo", timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"FP={fp}" in r.stdout
    assert f"DW={len(dw_units)}" in r.stdout
    np.testing.assert_allclose(np.load(ypath), y_live, rtol=1e-5, atol=1e-6)


def test_compress_cli_writes_loadable_artifact(tmp_path):
    out = os.path.join(str(tmp_path), "cli.npz")
    r = subprocess.run(
        [sys.executable, "-m", "repro.compress", "--arch", "tiny_mobilenet",
         "--budget-ratio", "0.7", "--P", "100", "--out", out],
        capture_output=True, text=True, env=_SUBPROC_ENV, cwd="/root/repo",
        timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    art = runtime.load(out)
    assert art.graph.family == "cnn"
    assert art.meta["source"]["arch"] == "tiny_mobilenet"
    assert art.plan is not None and len(art.plan.segments) >= 1
    x = jnp.zeros((1, 16, 16, 3))
    assert art.apply(x).shape == (1, 4)
