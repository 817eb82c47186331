"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed beside the CPU backend, so a kernel can be
compiled for a chip that is described and not attached.  This finds what
interpret mode cannot: Mosaic refuses a DMA window or a block that is not
aligned to its (8, 128) tiling, and asks for fast memory a kernel may not
use.  Nothing runs, so these tests say nothing about values or times.

Every shape below is one that Mosaic refused before the kernels padded
channels to whole lanes and widths to whole sublanes: the ResNet-34 stem
(Cin 3), a 3×3 conv at Cin 64, a stride-2 3×3 whose DMA window was 29
rows wide, a merged 7×7 with a 7×7 output at 512 channels, MobileNetV2's
depthwise conv at 96 channels, and the rank-merged FFN at smollm's
D = 576.  MobileNetV2's strided 32 → 96 merge is compiled beside them,
and the stem is checked to run folded (``ops.fold_taps``): its patch
written once at 256 lanes, the 3-channel image never at 128.  The last
test compiles a whole merged network under a four-chip
mesh: XLA cannot partition a Mosaic kernel, so the executor must run each
one per data shard.  The tiny plans of the benchmark's tests are compiled
for one chip to see that the kernels' names and the executor's unit and
role scopes reach the compiled text.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.kernels import ops
from _tiny_plans import PLANS, tiny_graph

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *shapes):
    with ops.force_backend("pallas"):
        return jax.jit(fn).lower(*shapes).compile().as_text()


# (name, NHWC input, HWIO weight, stride, depthwise)
CONV_CASES = [
    ("stem_7x7_s2_cin3", (2, 230, 230, 3), (7, 7, 3, 64), 2, False),
    ("3x3_cin64", (2, 58, 58, 64), (3, 3, 64, 64), 1, False),
    ("3x3_s2_cin256_window29", (2, 58, 58, 256), (3, 3, 256, 256), 2,
     False),
    ("merged_7x7_out7_c512", (2, 13, 13, 512), (7, 7, 512, 512), 1, False),
    ("depthwise_3x3_s2_c96", (2, 114, 114, 96), (3, 3, 1, 96), 2, True),
    ("mbv2_unit01_3x3_s2_cin32", (2, 114, 114, 32), (3, 3, 32, 96), 2,
     False),
]


# fp32 weights, int8 weights, and int8 weights with int8 activations.
# With int8 activations the conv kernels take up to a minute each to
# compile at these sizes, so they are compiled with fp32 and int8
# weights only.
FFN_MODES = ["none", "int8", "w8a8"]
CONV_MODES = ["none", "int8"]


@pytest.mark.parametrize("mode", CONV_MODES)
@pytest.mark.parametrize("name,xs,ws,stride,dw", CONV_CASES,
                         ids=[c[0] for c in CONV_CASES])
def test_conv_kernel_compiles(one_chip, name, xs, ws, stride, dw, mode):
    quant = mode != "none"
    op = kernels.depthwise_conv_op if dw else kernels.merged_conv_op

    def fn(x, w, b, scale):
        return op(x, w, b, stride=stride, activation="relu",
                  w_scale=scale if quant else None, act_quant=mode)

    cout = ws[-1]
    wdt = jnp.int8 if quant else jnp.float32
    hlo = _hlo(fn, jax.ShapeDtypeStruct(xs, jnp.float32, sharding=one_chip),
               jax.ShapeDtypeStruct(ws, wdt, sharding=one_chip),
               jax.ShapeDtypeStruct((cout,), jnp.float32, sharding=one_chip),
               jax.ShapeDtypeStruct((cout,), jnp.float32, sharding=one_chip))
    assert hlo.count(KERNEL) == 1


_OUT = re.compile(r"\s*(?:ROOT )?%\S+ = (.*?) [\w\-]+\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]\{([\d,]*)")


@pytest.mark.parametrize("batch", [2, 128])
@pytest.mark.parametrize("mode", CONV_MODES)
def test_stem_folds_its_taps_into_the_contraction(one_chip, mode, batch):
    """The 7×7 stride-2 stem at Cin 3 runs folded, with its taps gathered
    at batch 2 and batch-major at 128: its patch build is named
    ``fold_taps``, the kernel's image operand carries K = 256 lanes (147
    real), and no instruction writes the 3-channel image, or a tap of it,
    out at 128 lanes: neither padded to 128 channels, as the tap path's
    lane pad did, nor with its 3 channels on the lane axis.  The patch
    itself is written once: one instruction has an output as large as
    its 147 real channels."""
    quant = mode != "none"
    (n, h, w, _), ws = (batch, 230, 230, 3), (7, 7, 3, 64)

    def fn(x, wt, b, scale):
        return kernels.merged_conv_op(x, wt, b, stride=2, activation="relu",
                                      w_scale=scale if quant else None,
                                      act_quant=mode)

    wdt = jnp.int8 if quant else jnp.float32
    hlo = _hlo(fn, jax.ShapeDtypeStruct((n, h, w, 3), jnp.float32,
                                        sharding=one_chip),
               jax.ShapeDtypeStruct(ws, wdt, sharding=one_chip),
               jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip),
               jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip))
    entry = hlo[hlo.index("\nENTRY"):]
    assert "/relayout/fold_taps/" in entry
    call = next(l for l in entry.splitlines() if KERNEL in l)
    image = re.search(r"operand_layout_constraints=\{\w+\[([\d,]*)\]",
                      call).group(1)
    assert int(image.split(",")[-1]) == 256, call[:200]
    patch_writes = 0
    for line in entry.splitlines():
        out = _OUT.match(line)
        arrays = _ARRAY.findall(out.group(1) if out else "")
        patch_writes += any(np.prod([int(d) for d in dims.split(",") if d])
                            >= n * 112 * 112 * 147 for dims, _ in arrays)
        for dims, layout in arrays:
            dims = [int(d) for d in dims.split(",") if d]
            if len(dims) < 4:
                continue
            pixels = np.prod(dims[:-1])
            lanes_c = int(layout.split(",")[0]) == len(dims) - 1
            assert not (dims[-1] == 128 and pixels >= n * h * w), line[:160]
            assert not (dims[-1] == 3 and lanes_c
                        and pixels >= n * 112 * 112), line[:160]
    assert patch_writes == 1


@pytest.mark.parametrize("mode", FFN_MODES)
def test_merged_ffn_compiles_at_d576(one_chip, mode):
    quant = mode != "none"
    d, r = 576, 192

    def fn(x, u, v, us, vs):
        return kernels.merged_ffn_op(x, u, v, u_scale=us if quant else None,
                                     v_scale=vs if quant else None,
                                     act_quant=mode)

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    wdt = jnp.int8 if quant else jnp.float32
    hlo = _hlo(fn, s((4, 96, d)), s((d, r), wdt), s((r, d), wdt), s((r,)),
               s((d,)))
    assert hlo.count(KERNEL) == 1


def test_sharded_cnn_executor_compiles_on_four_chips(topo):
    """A merged ResNet under a data-parallel 2x2 mesh: one kernel per conv
    unit, each run per data shard, with no collective in the forward."""
    from repro.core import compress
    from repro.models import cnn, cnn_host, zoo
    from repro.runtime import executor, ir
    from repro.sharding.rules import make_unit_rules, use_rules

    net = zoo.tiny_resnet(num_classes=4, in_hw=16, width=8, blocks=(2, 2))
    host = cnn_host.CNNHost(net, cnn.init_params(net, jax.random.PRNGKey(0)),
                            batch=8)
    res = compress(host, budget_ratio=0.7, P=50)
    graph = res.lower()
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rules = make_unit_rules(mesh)
    params = ir.graph_params(graph)
    shapes = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        params, executor.graph_shardings(rules, graph))
    x = jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    with use_rules(rules):
        hlo = _hlo(lambda p, x: executor.execute(graph, x, params=p),
                   shapes, x)
    n_conv = sum(u.kind == "conv" for u in graph.units)
    assert n_conv > 0 and hlo.count(KERNEL) == n_conv
    assert "all-gather(" not in hlo


ROLE_SCOPE = re.compile(r"/unit\d\d/(pad|lane_pad|weight_prep|relayout|"
                        r"kernel|crop|epilogue)/|/head/")


@pytest.mark.parametrize("zoo_name,plan_file", PLANS,
                         ids=[p for _, p in PLANS])
def test_cnn_executor_names_reach_the_compiled_forward(one_chip, zoo_name,
                                                        plan_file):
    """On the chip's compiler each kernel is named after its kind, and
    every instruction made from the forward's ops carries its unit and
    role (or ``head``) in its ``op_name``; only the parameters' own copies
    and prefetches are left without one."""
    from repro.runtime import executor, ir

    net, graph = tiny_graph(zoo_name, plan_file)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        ir.graph_params(graph))
    x = jax.ShapeDtypeStruct((2, net.in_hw, net.in_hw, net.in_ch),
                             jnp.float32, sharding=one_chip)
    hlo = _hlo(lambda p, x: executor.execute(graph, x, params=p), shapes, x)
    entry = hlo[hlo.index("\nENTRY"):]
    kernels = 0
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if not m:
            continue
        if KERNEL in line:
            kernels += 1
            kind = m.group(1).split(".")[0]
            assert kind in ("merged_conv", "depthwise_conv"), line[:120]
            assert re.search(rf"/unit\d\d/kernel/{kind}/pallas_call$",
                             op.group(1)), op.group(1)
        elif op and "jit(" in op.group(1):
            assert ROLE_SCOPE.search(op.group(1)), (m.group(1), op.group(1))
    assert kernels == sum(u.kind == "conv" for u in graph.units)
