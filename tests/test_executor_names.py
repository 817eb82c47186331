"""The names the executor gives its work, and its count of traces.

Each CNN unit runs under ``unitNN`` and each of its ops under one role
scope (see ``repro.runtime.executor``); the kernels are named after their
kind.  Here the forward of the three tiny plans the benchmark's tests
use is traced with the Pallas path forced, so the pads, relayouts and
crops around each kernel are there, and every equation's name stack is
read.  Nothing is lowered: the compiled text is checked for a described
chip in ``test_tpu_compile.py``.
"""
import gc
import weakref

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.runtime import executor

from _tiny_plans import PLANS, tiny_graph

ROLES = {"pad", "lane_pad", "weight_prep", "relayout", "kernel", "crop",
         "epilogue"}


def _scopes(graph, x):
    """(primitive, name stack) of every equation of the forward, with the
    Pallas path forced."""
    with ops.force_backend("pallas"):
        jp = jax.make_jaxpr(lambda x: executor.execute(graph, x))(x)
    return [(e.primitive.name, str(e.source_info.name_stack))
            for e in jp.jaxpr.eqns]


def _expected_roles(u, x_shape) -> set:
    """Roles a conv unit cannot do without, from its shapes.  A narrow
    input that :func:`ops.fold_taps` folds is lane-padded inside its
    patch build, under ``relayout``."""
    kh, _, cin_g, _ = u.params["w"].shape
    n, h, w, cin = x_shape
    want = {"weight_prep", "relayout", "kernel"}
    if kh > 1:
        want.add("pad")
    if not u.depthwise and cin % 128 and not ops.fold_taps(
            (n, h + kh - 1, w + kh - 1, cin), u.params["w"].shape, u.stride):
        want.add("lane_pad")
    if u.add_from is not None or u.act not in (None, "none"):
        want.add("epilogue")
    return want


@pytest.mark.parametrize("zoo_name,plan_file", PLANS,
                         ids=[p for _, p in PLANS])
def test_every_unit_op_has_one_role(zoo_name, plan_file):
    net, graph = tiny_graph(zoo_name, plan_file)
    x = jnp.zeros((2, net.in_hw, net.in_hw, net.in_ch), jnp.float32)
    eqns = _scopes(graph, x)
    seen: dict[str, set] = {}
    kernels = []
    for prim, stack in eqns:
        parts = stack.split("/")
        if parts[0] == "head":
            continue
        assert parts[0].startswith("unit") and len(parts) >= 2, (prim, stack)
        assert parts[1] in ROLES, (prim, stack)
        assert not ROLES & set(parts[2:]), (prim, stack)  # one role only
        seen.setdefault(parts[0], set()).add(parts[1])
        if prim == "pallas_call":
            kernels.append((parts[0], parts[1:]))
    assert any(s == "head" for _, s in eqns)
    shapes = jax.eval_shape(
        lambda x: _unit_inputs(graph, x), x)
    for i, u in enumerate(graph.units):
        unit = f"unit{i:02d}"
        if u.kind != "conv":
            continue
        assert _expected_roles(u, shapes[i].shape) <= seen[unit], unit
        n, h, w, cin = shapes[i].shape
        kh = u.params["w"].shape[0]
        folded = not u.depthwise and ops.fold_taps(
            (n, h + kh - 1, w + kh - 1, cin), u.params["w"].shape, u.stride)
        fold_scope = f"{unit}/relayout/fold_taps/"
        assert folded == any(f"{st}/".startswith(fold_scope)
                             for _, st in eqns), unit
        kind = "depthwise_conv" if u.depthwise else "merged_conv"
        assert (unit, ["kernel", kind]) in kernels
    assert len(kernels) == sum(u.kind == "conv" for u in graph.units)


def _unit_inputs(graph, x):
    """The activation each unit receives (oracle path)."""
    saved, outs = {}, []
    if graph.meta.get("save_input"):
        saved[0] = x
    for u in graph.units:
        outs.append(x)
        x = executor._cnn_unit(u, x, saved)
        if u.save_at is not None:
            saved[u.save_at] = x
    return outs


def test_traces_count_one_per_input_shape():
    net, graph = tiny_graph("tiny_resnet", "tiny_resnet.plan.json")
    ex = executor.GraphExecutor(graph)
    x2 = jnp.zeros((2, net.in_hw, net.in_hw, net.in_ch), jnp.float32)
    x3 = jnp.zeros((3, net.in_hw, net.in_hw, net.in_ch), jnp.float32)
    for x in (x2, x2, x3, x2, x3):
        ex.apply(x).block_until_ready()
    assert ex.traces == {("apply", x2.shape): 1, ("apply", x3.shape): 1}


def test_dropping_an_executor_frees_it_without_the_cycle_collector():
    """The traced body closes over the counter, not the executor, so no
    reference cycle keeps the executor's device weights alive."""
    net, graph = tiny_graph("tiny_resnet", "tiny_resnet.plan.json")
    ex = executor.GraphExecutor(graph)
    x = jnp.zeros((2, net.in_hw, net.in_hw, net.in_ch), jnp.float32)
    ex.apply(x).block_until_ready()
    ref = weakref.ref(ex)
    gc.disable()
    try:
        del ex
        assert ref() is None
    finally:
        gc.enable()
