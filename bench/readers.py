"""What the per-layer readers in ``bench/metrics/`` compute, once.

Each reader file names one metric of ``BENCHMARK.json`` and calls one of
these on a :class:`bench.harness.MetricContext`.  A reader that finds
nothing to read returns None, and the harness leaves the metric out.
"""
from __future__ import annotations

import statistics

from bench import scopes
from bench.cost import kernel_bound_seconds
from bench.traces import APPLY


def kernel_roofline(ctx, kernel: str):
    """Per cent of its roofline that ``kernel`` reached in the traced
    window: for every traced call, each of the plan's ``kernel`` units in
    each of the call's ``forwards`` forwards could take no less than the
    larger of its operations over peak FLOP/s and its bytes over peak
    bytes/s (bytes the program keeps in on-chip memory do not count
    against the HBM), summed, over the summed device time of the
    ``kernel`` calls.  None where the trace has no such call, or where the
    first program's Pallas calls do not line up one for one with the
    plan's kernel units repeated ``forwards`` times."""
    t = ctx.trace.kernel_s.get(kernel, 0.0)
    ku = [u["kernel"] for u in ctx.work["units"] if u["kernel"]]
    order = ctx.trace.kernel_order
    if t <= 0 or not ku or [k for k, _ in order] != ku * ctx.forwards:
        return None
    fracs = [f for _, f in order]
    bound = sum(kernel_bound_seconds(ctx.work["units"], kernel, ctx.batch,
                                     ctx.peak, fracs[i:i + len(ku)])
                for i in range(0, len(fracs), len(ku)))
    return 100.0 * ctx.calls * bound / t


def outside_kernel_share(ctx):
    """Per cent of device-busy time in operations that are not Pallas
    kernels (padding, residual adds, projections, relayouts, pooling,
    activations, the head)."""
    if ctx.trace.op_s <= 0:
        return None
    kernels = sum(ctx.trace.kernel_s.values())
    return 100.0 * (ctx.trace.op_s - kernels) / ctx.trace.op_s


def idle_share(ctx):
    """Per cent of the traced window in which no operation ran on the
    device: 1 - (union of device-operation intervals) / window."""
    if ctx.trace.window_s <= 0 or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def mfu(ctx):
    """Per cent of the chips' peak FLOP/s that the whole forward reached:
    the plan's operations per image (``work.flops_per_image``, float32
    work held against the bf16 peak) times the forwards a call makes of
    each image, times the untraced window's images per second."""
    if ctx.images_per_s <= 0:
        return None
    return (100.0 * ctx.work["flops_per_image"] * ctx.forwards
            * ctx.images_per_s / (ctx.chips * ctx.peak["flops_per_s"]))


def layout_share(ctx):
    """Per cent of device-op time scoped ``pad``, ``lane_pad``,
    ``weight_prep``, ``relayout`` or ``crop``, or in an instruction made
    from these only (``bench.scopes.LAYOUT`` and ``LAYOUT_MIX``)."""
    if ctx.roles is None:
        return None
    return ctx.roles.share(scopes.LAYOUT + (scopes.LAYOUT_MIX,))


def epilogue_share(ctx):
    """Per cent of device-op time scoped ``epilogue`` (bias, activation,
    residual add) alone; an instruction that fuses an epilogue with
    another role is ``mixed`` and counts in neither share."""
    if ctx.roles is None:
        return None
    return ctx.roles.share(("epilogue",))


def dispatch_ms(ctx):
    """Median over the traced calls of the program's ``executor.apply``
    host span, in milliseconds: the host's time to hand a call to the
    device."""
    spans = (ctx.host or {}).get(APPLY)
    if not spans:
        return None
    return statistics.median(d for _, d in spans) * 1e-6

