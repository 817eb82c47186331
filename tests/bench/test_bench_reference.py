"""The benchmark's plain reference against the program, at toy sizes.

The reference (``bench/cnn_reference.py``) imports nothing of the
program; here it is held to the program's replaced network and to the
merged executor, on the CPU, for plans that keep, prune and merge.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cnn, cnn_reference as R, cost

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CASES = [("tiny_resnet", "tiny_resnet.plan.json"),
         ("tiny_resnet", "tiny_resnet_pruned.plan.json"),
         ("tiny_mobilenet", "tiny_mobilenet.plan.json")]


def _load(name, plan):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, plan)) as f:
        text = f.read()
    return cfg, text, json.loads(text)


def _rel(y, ref):
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(y - ref).max() / np.abs(ref).max())


@pytest.mark.parametrize("name,plan", CASES)
def test_reference_matches_program(name, plan, tmp_path):
    """Reference == the program's replaced network == the merged executor
    (the jnp oracle path off the TPU), to float32 rounding."""
    from repro.core.plan import CompressionPlan
    from repro.models import cnn as program_cnn

    cfg, text, pj = _load(name, plan)
    params = cnn.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (3, cfg["in_hw"], cfg["in_hw"], cfg["in_ch"]))
    ref = R.forward(cfg, params, x, pj)
    net = cnn._zoo_net(cfg)
    replaced = program_cnn.apply_replaced(net, params, x,
                                          CompressionPlan.from_json(text))
    assert _rel(replaced, ref) < 1e-6
    ex = cnn.build(cfg, params, text, str(tmp_path))
    assert _rel(ex.apply(x), ref) < 1e-5


def test_three_passes_differ_from_highest():
    """The control arithmetic drops the low halves' product: it differs
    from full float32 by about 2**-17 relative, and a bfloat16-exact
    operand pair is reproduced exactly."""
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    full = R.matmul(a, b)
    three = R.matmul(a, b, passes=3)
    err = _rel(three, full)
    assert 1e-7 < err < 1e-4
    ab = a.astype(jnp.bfloat16).astype(jnp.float32)
    bb = b.astype(jnp.bfloat16).astype(jnp.float32)
    assert _rel(R.matmul(ab, bb, passes=3), R.matmul(ab, bb)) < 1e-7


def test_identity_segment_is_identity():
    """A segment with no kept conv passes its input through unchanged."""
    cfg, _, pj = _load("tiny_resnet", "tiny_resnet_pruned.plan.json")
    seg = pj["segments"][2]
    assert seg["kept"] == [] and R.geometry(cfg, seg) == (1, 1)
    (u,) = [u for u in cost.units(cfg, pj) if u["unit"] == "conv3_5"]
    assert u["kernel"] == "depthwise_conv"


def test_init_params_seeded():
    cfg, _, _ = _load("tiny_mobilenet", "tiny_mobilenet.plan.json")
    a = cnn.init_params(cfg, jax.random.PRNGKey(5))
    b = cnn.init_params(cfg, jax.random.PRNGKey(5))
    c = cnn.init_params(cfg, jax.random.PRNGKey(6))
    eq = jax.tree.map(lambda p, q: bool(jnp.array_equal(p, q)), a, b)
    assert all(jax.tree.leaves(eq))
    assert not jnp.array_equal(a["head"]["w"], c["head"]["w"])
