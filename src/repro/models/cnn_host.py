"""Host adapter: plan-aware CNNs → the generic LayerMerge core.

Implements the full batched-probe protocol of
:mod:`repro.core.probe_engine`: shape signatures for latency bucketing,
AOT-lowerable probe callables, Dirac-masked span batches for vmapped
importance fine-tunes, and a content fingerprint for the table cache.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp

from repro import kernels
from repro.core import table_cache
from repro.core.latency import CostBreakdown, conv2d_cost
from repro.core.plan import CompressionPlan, LayerDesc, Segment
from repro.core.probe_engine import ProbeCallable
from repro.core.segments import SegmentEnumerator
from repro.runtime import executor, ir

from . import cnn


def _dirac_like(w: jax.Array, depthwise: bool) -> jax.Array:
    """Identity stand-in for a pruned conv, at the conv's OWN kernel shape.

    A ``k×k`` kernel that is a centred delta (times the channel identity)
    computes *exactly* the input center-crop — every off-center tap
    multiplies by 0.0 and the center tap by 1.0, so the output is bitwise
    the input.  Substituting it for a pruned conv inside an all-kept span
    graph reproduces the true replaced network (which pads less and skips
    the layer) while keeping one shared trace for every kept-set of the
    span — the structural trick behind the vmapped importance batch.
    Requires odd ``k`` (centred delta) — the host's eligibility check.
    """
    kh, kw, cin, cout = w.shape
    c0, c1 = (kh - 1) // 2, (kw - 1) // 2
    if depthwise:
        return jnp.zeros_like(w).at[c0, c1, 0, :].set(1.0)
    return jnp.zeros_like(w).at[c0, c1].set(jnp.eye(cin, cout,
                                                    dtype=w.dtype))


@dataclasses.dataclass
class CNNHost:
    net: cnn.ConvNet
    params: dict                      # pre-trained parameters
    batch: int = 8                    # batch size for cost/latency accounting
    dtype_bytes: int = 2
    max_span: int | None = None
    # Split weight- vs. activation-byte widths for the cost model; None
    # defaults to ``dtype_bytes`` (the historical single-scalar behavior,
    # bit-identical).  Per-segment quantization overrides both via
    # ``segment_cost(seg, quant=...)``.
    w_bytes: int | None = None
    act_bytes: int | None = None

    def __post_init__(self):
        self._descs = self.net.layer_descs(self.params)
        self._shapes = self.net.boundary_shapes()

    # -- core protocol ---------------------------------------------------------
    def descs(self) -> list[LayerDesc]:
        return self._descs

    def enumerator(self, method: str = "layermerge") -> SegmentEnumerator:
        return SegmentEnumerator(
            self._descs, offset=1, cap=None,
            allowed_span=self.net.allowed_span,
            depth_mode=(method == "depth"),
            max_span=self.max_span)

    def original_k(self, l: int) -> int:
        return self._descs[l - 1].growth + 1

    def pruned_k(self, l: int) -> int:
        return 1

    # -- latency ----------------------------------------------------------------
    def segment_cost(self, seg: Segment, quant: str = "none"
                     ) -> CostBreakdown | None:
        """Analytic cost of the merged segment at its true input shape.

        ``quant`` (or ``seg.quant``) prices the segment at narrow byte
        widths — int8/fp8 weights, int8 activations under 'w8a8'.
        Returns ``None`` when a quantized cost is requested for a
        segment the quantized kernels cannot execute (non-conv barrier
        units), which is how the table builder skips ineligible spans.
        """
        q = quant if quant != "none" else seg.quant
        h, w, cin = self._shapes[seg.i]
        _, _, cout = self._shapes[seg.j]
        s_last = self.net.spec(seg.j)
        if s_last.kind != "conv":
            if q != "none":
                return None
            if s_last.kind == "attn":
                n = h * w
                c = cin
                flops = 4 * 2 * n * c * c + 2 * n * n * c * 2
                return CostBreakdown(flops * self.batch,
                                     4 * n * c * self.dtype_bytes * self.batch)
            return CostBreakdown(0.0, h * w * cin * self.dtype_bytes
                                 * self.batch * 2)
        K, S = cnn.segment_geometry(self.net, seg)
        kept = set(seg.kept)
        dw = all(self.net.spec(l).depthwise for l in seg.layers
                 if l in kept and self.net.spec(l).kind == "conv") and kept
        wb = kernels.quant.weight_bytes(q) or self.w_bytes
        ab = kernels.quant.act_bytes(q) or self.act_bytes
        return conv2d_cost(h, w, cin, cout, K, stride=S, depthwise=bool(dw),
                           dtype_bytes=self.dtype_bytes, batch=self.batch,
                           w_bytes=wb, act_bytes=ab)

    def probe_signature(self, seg: Segment):
        """Shape signature bucketing this segment's latency probe.

        Captures every input of both ``segment_cost`` and the wall-clock
        callable's trace — input shape, output channels, merged geometry
        ``(K, S)``, depthwise-ness, batch, and dtype width — so any two
        segments with equal signatures are latency-identical by
        construction and one measurement serves the whole bucket.
        """
        h, w, cin = self._shapes[seg.i]
        _, _, cout = self._shapes[seg.j]
        s_last = self.net.spec(seg.j)
        if s_last.kind != "conv":
            return (s_last.kind, h, w, cin, s_last.k, s_last.stride,
                    self.batch, self.dtype_bytes, self.w_bytes,
                    self.act_bytes)
        K, S = cnn.segment_geometry(self.net, seg)
        kept = set(seg.kept)
        dw = all(self.net.spec(l).depthwise for l in seg.layers
                 if l in kept and self.net.spec(l).kind == "conv") and kept
        # feature_group_count rides in the signature explicitly: depthwise
        # segments bucket by their group count (= cin under the phase-major
        # grouped kernel), never alongside dense segments of equal shape.
        groups = cin if dw else 1
        return ("conv", h, w, cin, cout, K, S, bool(dw), groups, self.batch,
                self.dtype_bytes, self.w_bytes, self.act_bytes)

    def segment_probe(self, seg: Segment, params=None) -> ProbeCallable:
        """Jitted merged-segment forward as (fn, args) — AOT-lowerable."""
        params = params or self.params
        h, w, cin = self._shapes[seg.i]
        x = jnp.zeros((self.batch, h, w, cin), jnp.float32)
        s_last = self.net.spec(seg.j)
        if s_last.kind != "conv":
            if s_last.kind == "attn":
                return ProbeCallable(jax.jit(cnn._tiny_self_attention),
                                     (x, params["layers"][seg.j - 1]))
            if s_last.kind == "pool":
                @jax.jit
                def pool_fn(x):
                    return jax.lax.reduce_window(
                        x, 0.0, jax.lax.add, (1, s_last.k, s_last.k, 1),
                        (1, s_last.stride, s_last.stride, 1),
                        "SAME") / (s_last.k * s_last.k)
                return ProbeCallable(pool_fn, (x,))

            @jax.jit
            def up_fn(x):
                n, hh, ww, c = x.shape
                return jax.image.resize(
                    x, (n, hh * s_last.stride, ww * s_last.stride, c),
                    "nearest")
            return ProbeCallable(up_fn, (x,))
        wgt, b, stride, dw = cnn.merge_segment(self.net, params["layers"], seg)
        K = wgt.shape[0]
        lo, hi = (K - 1) // 2, (K - 1) - (K - 1) // 2

        @jax.jit
        def fn(x, wgt, b):
            xp = jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0))) if K > 1 else x
            # Time the segment exactly as it deploys at this batch:
            # through the Pallas fast path on TPU (strided and depthwise
            # segments included), oracle off-TPU.  A folded narrow input
            # builds its patch by batch (ops.fold_batch_major).
            if dw:
                return kernels.depthwise_conv_op(xp, wgt, b, stride=stride)
            return kernels.merged_conv_op(xp, wgt, b, stride=stride)
        return ProbeCallable(fn, (x, wgt, b))

    def segment_callable(self, seg: Segment, params=None):
        """Zero-arg jitted merged-segment forward for wall-clock timing."""
        probe = self.segment_probe(seg, params)
        return lambda: probe.fn(*probe.args)

    # -- batched importance probes ---------------------------------------------
    def importance_batch(self, segs: list[Segment], params=None):
        """One shared apply + stacked candidates for a span's Eq. 4 probes.

        Every probe of span ``(i, j]`` is expressed on ONE graph — the
        all-kept replaced network — by substituting a centred Dirac kernel
        (an exact identity, see :func:`_dirac_like`) for each pruned conv
        and zeroing its bias.  The candidates then differ only in leaf
        VALUES, so the engine can stack them and vmap the fine-tune.  The
        returned ``grad_mask`` freezes the Dirac leaves: updating them
        would turn "no layer" into a free extra conv and change Eq. 4's
        semantics.  Returns None (sequential fallback) when the span holds
        non-conv units, normed convs (BN/GN folding changes the fine-tune
        parametrization), or even kernels (no centred delta).
        """
        from repro.core.tables import one_segment_plan

        params = params or self.params
        seg0 = segs[0]
        span = tuple(range(seg0.i + 1, seg0.j + 1))
        for l in span:
            s = self.net.spec(l)
            if s.kind != "conv" or s.norm is not None or s.k % 2 == 0:
                return None
        probe = Segment(i=seg0.i, j=seg0.j, k=0, kept=span)
        K_all, _ = cnn.segment_geometry(self.net, probe)
        probe = Segment(i=seg0.i, j=seg0.j, k=K_all, kept=span)
        apply_fn, _ = self.replaced_apply(one_segment_plan(self, probe),
                                          params)
        ones = jax.tree.map(lambda x: jnp.ones((), x.dtype), params)
        cands, masks = [], []
        for seg in segs:
            kept = set(seg.kept)
            layers = list(params["layers"])
            mlayers = list(ones["layers"])
            for l in span:
                if l in kept:
                    continue
                s = self.net.spec(l)
                p, mp = dict(layers[l - 1]), dict(mlayers[l - 1])
                p["w"] = _dirac_like(p["w"], s.depthwise)
                mp["w"] = jnp.zeros((), p["w"].dtype)
                if "b" in p:
                    p["b"] = jnp.zeros_like(p["b"])
                    mp["b"] = jnp.zeros((), p["b"].dtype)
                layers[l - 1], mlayers[l - 1] = p, mp
            cands.append({**params, "layers": layers})
            masks.append({**ones, "layers": mlayers})
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *cands)
        grad_mask = jax.tree.map(lambda *xs: jnp.stack(xs), *masks)
        return apply_fn, stacked, grad_mask

    def fingerprint(self) -> str:
        """Content digest for the on-disk table cache: network structure,
        probe workload, parameter bytes, and machine identity (wall-clock
        latencies do not transfer across hosts)."""
        h = hashlib.sha256()
        # w_bytes/act_bytes ride in the digest so tables priced under the
        # old single-scalar cost model are never silently reused.
        h.update(repr((self.net, self.batch, self.dtype_bytes,
                       self.max_span, self.w_bytes,
                       self.act_bytes)).encode())
        h.update(table_cache.pytree_digest(self.params).encode())
        h.update(table_cache.machine_token().encode())
        return h.hexdigest()

    # -- plan lowering / network builders -----------------------------------------
    def lower_plan(self, plan: CompressionPlan, params=None) -> ir.UnitGraph:
        """Lower a plan to the shared unit IR (Algorithm 2 final step).

        Folds every conv segment into one merged convolution
        (:func:`repro.models.cnn.merge_segment`: Eq. 1 composition, BN
        folding, skip-add Dirac fusion) and emits typed unit records with
        explicit skip/concat wiring, group-norm and boundary-activation
        epilogues — the executable, serializable form of the plan.
        """
        params = params or self.params
        net = self.net
        layers = params["layers"]
        need_save = {sk.start for sk in net.skips}
        add_end = {sk.end: (sk.start, i) for i, sk in enumerate(net.skips)
                   if sk.kind == "add"}
        cat_end = {sk.end: sk.start for sk in net.skips
                   if sk.kind == "concat"}
        units = []
        for seg in plan.segments:
            s_last = net.spec(seg.j)
            save_at = seg.j if seg.j in need_save else None
            if s_last.kind != "conv":
                assert seg.j - seg.i == 1, "barriers are singleton segments"
                if s_last.kind == "pool":
                    units.append(ir.PoolUnit(
                        k=s_last.k, stride=s_last.stride,
                        concat_from=cat_end.get(seg.j), save_at=save_at))
                elif s_last.kind == "upsample":
                    units.append(ir.UpsampleUnit(
                        factor=s_last.stride,
                        concat_from=cat_end.get(seg.j), save_at=save_at))
                else:
                    units.append(ir.AttnUnit(
                        save_at=save_at, params=dict(layers[seg.j - 1])))
                continue
            w, b, stride, dw = cnn.merge_segment(net, layers, seg)
            gn, gn_groups = cnn._segment_gn(net, layers, seg)
            act = s_last.act
            if net.act_after_merge and not seg.original and act == "none":
                act = "relu6"
            if seg.j >= net.L:
                act = "none"          # σ_L is the identity (paper §2)
            uparams = {"w": w, "b": b}
            if seg.quant != "none":
                # Narrow weights + symmetric per-output-channel scale; the
                # scale is data and serializes like any param (artifact v3).
                wq, wsc = kernels.quant.quantize_weight(w, seg.quant, axis=3)
                uparams = {"w": wq, "b": b, "w_scale": wsc}
            add_from = None
            proj_stride = 1
            if seg.j in add_end:
                # skip-adds whose block starts inside the segment were
                # Dirac-fused by merge_segment (proj blocks never fuse)
                src, ski = add_end[seg.j]
                sk = net.skips[ski]
                if src < seg.i or sk.proj:
                    add_from = src
                    if sk.proj:
                        uparams["proj"] = dict(params["skips"][ski])
                        proj_stride = cnn._skip_stride(net, sk)
            if gn is not None:
                uparams["gn"] = dict(gn)
            units.append(ir.ConvUnit(
                stride=stride, depthwise=dw, act=act, gn_groups=gn_groups,
                proj_stride=proj_stride, add_from=add_from,
                concat_from=cat_end.get(seg.j), save_at=save_at,
                quant=seg.quant, params=uparams))
        gparams = {}
        if net.head == "classifier":
            gparams["head"] = dict(params["head"])
        return ir.annotate_axes(ir.UnitGraph(
            family="cnn", units=tuple(units), params=gparams,
            meta={"save_input": 0 in need_save, "head": net.head}))

    def replaced_apply(self, plan: CompressionPlan, params=None):
        params = params or self.params

        def apply_fn(p, x):
            return cnn.apply_replaced(self.net, p, x, plan)
        return apply_fn, params

    def merged_apply(self, plan: CompressionPlan, params=None):
        """Merged forward through the shared runtime executor.

        ``apply_fn(p, x)`` re-lowers from ``p`` on every call (traced
        once under jit), so fine-tuned parameters flow straight into the
        merged weights exactly like the legacy closure did.
        """
        params = params or self.params

        def apply_fn(p, x):
            return executor.execute(self.lower_plan(plan, p), x)
        return apply_fn, params
