"""Reduction of a profiler trace to device time, kernel time and idle gaps.

A trace of the TPU holds, per chip, a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` has one event per executed HLO instruction, named by the
instruction's text (``%name = <shape> <opcode>(...)``), and whose line
``XLA Modules`` has one event per executed program (here: one per call).
The harness's host spans (``bench.issue``, ``bench.sync``) and the
program's (``executor.apply``) are on ``/host:CPU``.  Host and device
clocks differ by an offset that nothing records, so :func:`reduce` places
idle gaps by the device's own structure: between two programs the host
was returning from one call's sync and issuing the next, inside one it
was waiting in the sync.  (:func:`bench.scopes.align` bounds the offset,
to about half a millisecond, from the same spans.)

:func:`extract` turns a trace file into a small JSON-able record;
:func:`reduce` computes from that record what the per-layer metrics read.
Pallas kernels are the ``tpu_custom_call`` instructions of the compiled
forward; which kernel each one is comes from its name, or else from its
instruction text (see :func:`kernel_kind`).
"""
from __future__ import annotations

import collections
import dataclasses
import re

KERNELS = ("merged_conv", "depthwise_conv")
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
BETWEEN_CALLS = "between calls: host returns from bench.sync, runs bench.issue"
EDGES = "window edges: first issue, last bench.sync return"
APPLY, ISSUE, SYNC = "executor.apply", "bench.issue", "bench.sync"

_INSTR = re.compile(r"^\s*%?([\w.\-]+) = ")
_DIMS = re.compile(r"\b[a-z][a-z0-9]*\[([\d,]*)\]")
_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\](\{[^}]*\})?")
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "f16": 2, "bf16": 2, "s16": 2,
         "u16": 2, "f64": 8, "s64": 8, "u64": 8}


def _dims(text: str) -> list[tuple[int, ...]]:
    return [tuple(int(d) for d in m.split(",") if d)
            for m in _DIMS.findall(text)]


def _braced(text: str, key: str) -> str:
    """The text between ``key`` (ending in an open brace) and its match."""
    i = text.find(key)
    if i < 0:
        return ""
    depth, j = 1, i + len(key)
    for k in range(j, len(text)):
        depth += {"{": 1, "}": -1}.get(text[k], 0)
        if depth == 0:
            return text[j:k]
    return ""


def kernel_kind(name: str, text: str) -> str:
    """``merged_conv`` or ``depthwise_conv`` for a Pallas custom call.

    A kernel named after its kind (``pallas_call(name=...)``) is taken at
    its name.  Otherwise the operands tell: both kernels take (image,
    weight, bias); ``merged_conv``'s weight is (kh, kw, Cin, Cout) and its
    output has Cout channels, ``depthwise_conv``'s is group-blocked
    (kh, kw, G, Cin_g·Cout_g) and its output has G·Cout_g channels.
    """
    for k in KERNELS:
        if name.startswith(k):
            return k
    out = _dims(text.split(" custom-call(", 1)[0])
    ops = _dims(_braced(text, "operand_layout_constraints={"))
    if not out or len(ops) < 2 or len(ops[1]) != 4:
        return "pallas"
    return "merged_conv" if out[0][-1] == ops[1][-1] else "depthwise_conv"


def hbm_fraction(text: str) -> float:
    """Share of a Pallas call's operand and result bytes that the compiled
    program keeps in HBM.  XLA may place an array in the chip's on-chip
    memory (layout ``S(1)``); its bytes then never cross the HBM, and an
    HBM roofline that counted them would not bound the kernel's time."""
    if " custom-call(" not in text:
        return 1.0
    head, rest = text.split(" custom-call(", 1)
    args = rest.split(", custom_call_target=", 1)[0]
    total = hbm = 0
    for dt, dims, layout in _ARRAY.findall(head + " " + args):
        n = _ITEM.get(dt, 4)
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n
        if "S(1)" not in (layout or ""):
            hbm += n
    return hbm / total if total else 1.0


def kernel_kinds(hlo_text: str) -> dict[str, str]:
    """Instruction name → kernel kind, for every Pallas call in a module."""
    kinds = {}
    for line in hlo_text.splitlines():
        if CUSTOM_CALL in line:
            m = _INSTR.match(line)
            if m:
                kinds[m.group(1)] = kernel_kind(m.group(1), line)
    return kinds


def _instr(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name[:80]


def extract(trace) -> dict:
    """The parts of a trace that the readers use; ``trace`` is an
    ``.xplane.pb`` path or a ``jax.profiler.ProfileData``.  ``devices``
    and ``custom_calls`` are what :func:`reduce` reads; ``host`` holds the
    host spans :data:`APPLY`, :data:`ISSUE` and :data:`SYNC`, each a sorted
    list of ``[start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    devices, custom = [], {}
    host: dict[str, list] = {APPLY: [], ISSUE: [], SYNC: []}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append([e.start_ns, e.duration_ns])
        elif plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name = _instr(e.name)
                        if CUSTOM_CALL in e.name and name not in custom:
                            custom[name] = e.name
                        ops.append([name, e.start_ns, e.duration_ns])
                elif line.name == "XLA Modules":
                    modules = [[e.name, e.start_ns, e.duration_ns]
                               for e in line.events]
            if ops:
                devices.append({"plane": plane.name, "ops": ops,
                                "modules": modules})
    return {"devices": devices, "custom_calls": custom,
            "host": {k: sorted(v) for k, v in host.items()}}


@dataclasses.dataclass
class Reduced:
    """Device time of a traced window, averaged over the chips traced."""

    window_s: float          # host clock, first issue to last sync
    busy_s: float            # union of device-op intervals
    op_s: float              # sum of device-op durations
    kernel_s: dict           # kernel kind -> sum of its calls' durations
    top_ops: list            # [[op, seconds]], the 10 largest
    idle_gaps: list          # [[where, seconds]], the 10 largest
    kernel_order: list       # (kind, share of its bytes in HBM) of each
    #                          Pallas call of the first program, in order


def _union(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(ops, modules, window_s: float) -> dict[str, float]:
    """Idle time on one chip, by where it falls (seconds)."""
    ops = sorted(ops, key=lambda o: o[1])
    starts = [m[1] for m in modules]
    gaps: dict[str, float] = collections.Counter()
    reach = None
    for name, s, d in ops:
        if reach is not None and s > reach:
            crossed = any(reach <= m <= s for m in starts)
            where = BETWEEN_CALLS if crossed else f"inside a call, before {name}"
            gaps[where] += (s - reach) * 1e-9
        reach = s + d if reach is None else max(reach, s + d)
    span = (reach - ops[0][1]) * 1e-9 if ops else 0.0
    if window_s > span:
        gaps[EDGES] += window_s - span
    return gaps


def reduce(rec: dict, window_s: float, kinds: dict | None = None) -> Reduced:
    """Busy, kernel and idle time of a traced window of ``window_s`` s.

    ``kinds`` maps instruction names to kernel kinds (from the compiled
    forward, :func:`kernel_kinds`); calls it lacks are placed by their own
    instruction text.
    """
    kinds = dict(kinds or {})
    for name, text in rec.get("custom_calls", {}).items():
        kinds.setdefault(name, kernel_kind(name, text))
    devs = rec["devices"]
    if not devs:
        return Reduced(window_s, 0.0, 0.0, {}, [], [], [])
    texts = rec.get("custom_calls", {})
    n = len(devs)
    busy = op = 0.0
    kernel_s: dict[str, float] = collections.Counter()
    per_op: dict[str, float] = collections.Counter()
    for dev in devs:
        ops = dev["ops"]
        busy += sum(e - s for s, e in _union(
            (s, s + d) for _, s, d in ops)) * 1e-9
        for name, _, d in ops:
            op += d * 1e-9
            label = name
            if name in kinds:
                kernel_s[kinds[name]] += d * 1e-9
                label = f"{kinds[name]}:{name}"
            per_op[label] += d * 1e-9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = _gaps(devs[0]["ops"], devs[0]["modules"], window_s)
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    order = []
    if devs[0]["modules"]:
        _, m0, md = min(devs[0]["modules"], key=lambda m: m[1])
        order = [(kinds[name], hbm_fraction(texts.get(name, "")))
                 for name, st, _ in sorted(devs[0]["ops"], key=lambda o: o[1])
                 if name in kinds and m0 <= st <= m0 + md]
    return Reduced(
        window_s=window_s, busy_s=busy / n, op_s=op / n,
        kernel_s={k: v / n for k, v in kernel_s.items()},
        top_ops=[[k, v / n] for k, v in top],
        idle_gaps=[[k, v] for k, v in idle], kernel_order=order)
