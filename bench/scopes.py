"""The program's own names in a profiler trace: unit and role scopes of
device ops, and host spans put on the device's clock.

The program names its work (see ``repro.runtime.executor``): unit ``i``
of a CNN runs under the scope ``unitNN``, each of its ops under one role
(:data:`ROLES`), the classifier under ``head``, and every call of
``GraphExecutor.apply`` under the host span ``executor.apply``.  The
compiled forward keeps the scopes as each instruction's ``op_name``, and
the trace names device ops by instruction, so :func:`role_seconds` puts
each op's device time in its unit and role.  A fusion is attributed by
the ``op_name``s of the instructions inside it (:func:`op_names`): one
made from several roles is ``layout`` when all are layout roles, else
``mixed`` (:func:`scope_of`).  An instruction XLA merged from several
ops outside a fusion (a pad of a pad) keeps one ``op_name``, so the
split inside layout between ``pad``, ``lane_pad`` and ``relayout`` is
approximate.  Ops with no unit or ``head`` scope count as ``other``.

Host and device clocks differ by an offset that nothing records.
:func:`align` bounds it by causality: call ``i``'s program cannot start
on the device before the host enters ``executor.apply`` for it, nor end
after the host returns from its ``bench.sync``.  On the TPU v5e the
interval is about half a millisecond wide, wider than a batch-1
``executor.apply``, so device idle time cannot be placed under that span.

The harness reads a traced window through these functions
(``bench.harness.MetricContext.from_trace``) and logs the per-role table
(:func:`role_table`); the readers in ``bench/readers.py`` take their
shares from it.  :func:`write_fixture` records a traced window, trimmed,
as a test fixture: on the chip, build a ``bench.harness.Cell``, run its
``traced_window`` and pass the trace's ``ProfileData`` here.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import re

from bench.traces import APPLY, CUSTOM_CALL, ISSUE, SYNC

ROLES = ("pad", "lane_pad", "weight_prep", "relayout", "kernel", "crop",
         "epilogue")
LAYOUT = ("pad", "lane_pad", "weight_prep", "relayout", "crop")
# roles of one instruction made from ops of several: all layout roles, or
# anything else (a crop fused into an activation, say)
LAYOUT_MIX, MIXED = "layout", "mixed"
OTHER = "other"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# inside a fusion these do no work of their own, and CSE leaves them with
# the op_name of whichever op made them first, often in another unit
_NO_WORK = ("constant", "broadcast", "parameter")
_CALLS = re.compile(r"\bcalls=(\{[^}]*\}|%?[\w.\-]+)")
_UNIT = re.compile(r"unit\d+")


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name → the ``op_name``s it was made from, joined by
    ``;``: its own, then, for a fusion, those of the instructions of the
    computation it calls (nested fusions included; constants, broadcasts
    and parameters passed over), each once.  Covers every instruction of
    a compiled module's text that has any."""
    comps: dict[str, list] = {}
    instrs = {}              # name -> (opcode, own op_name, called)
    body = comps.setdefault("", [])
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            body = comps.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OPCODE.search(line)
        own = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        called = [] if not calls else [
            c.strip().lstrip("%") for c in calls.group(1).strip("{}")
            .split(",")]
        body.append(m.group(1))
        instrs[m.group(1)] = (op.group(1) if op else "",
                              own.group(1) if own else "", called)

    def names(n, seen):
        _, own, called = instrs[n]
        out = [x for x in own.split(";") if x]
        for c in called:
            if c not in seen:
                seen.add(c)
                for inner in comps.get(c, ()):
                    if instrs[inner][0] not in _NO_WORK:
                        out += names(inner, seen)
        return out

    out = {}
    for n in instrs:
        got = list(dict.fromkeys(names(n, set())))
        if got:
            out[n] = ";".join(got)
    return out


def _scope_of_one(op_name: str) -> tuple[str, str]:
    parts = op_name.split("/")
    for i, p in enumerate(parts[:-1]):
        if p == "head":
            return "head", "head"
        if _UNIT.fullmatch(p):
            role = next((q for q in parts[i + 1:-1] if q in ROLES), OTHER)
            return p, role
    return "", OTHER


def scope_of(op_name: str) -> tuple[str, str]:
    """``(unit, role)`` of an instruction from its ``op_name``s (``;``
    joined, as :func:`op_names` gives them): ``("unit07", "lane_pad")``,
    ``("head", "head")``, or ``("", "other")`` outside both.  The role of
    one ``op_name`` is the first role scope below its unit; the last
    component is the primitive, never a scope.  Names outside any unit
    and ``head`` (a parameter's) are passed over.  An instruction made
    from ops of several roles is ``layout`` when all are layout roles and
    ``mixed`` otherwise; its unit is that of its first name."""
    scopes = [s for s in map(_scope_of_one, op_name.split(";")) if s[0]]
    if not scopes:
        return "", OTHER
    roles = {r for _, r in scopes}
    if len(roles) == 1:
        return scopes[0]
    return scopes[0][0], LAYOUT_MIX if roles <= set(LAYOUT) else MIXED


@dataclasses.dataclass
class Roles:
    """Device-op time of a traced window by role and unit, averaged over
    the chips traced (seconds)."""

    op_s: float              # all device ops, the shares' denominator
    by_role: dict            # role -> seconds
    by_unit: dict            # role -> {unit -> seconds}
    other_ops: list          # [[instruction, seconds]], 10 largest 'other'

    def share(self, roles) -> float | None:
        """Per cent of device-op time in ``roles``; None with no ops."""
        if self.op_s <= 0:
            return None
        return 100.0 * sum(self.by_role.get(r, 0.0) for r in roles) \
            / self.op_s


def role_seconds(rec: dict, names: dict[str, str]) -> Roles:
    """Device time per role and unit of ``bench.traces.extract``'s record,
    with ``names`` from :func:`op_names` of the compiled forward."""
    by_role: dict[str, float] = collections.Counter()
    by_unit: dict[str, dict] = collections.defaultdict(collections.Counter)
    other: dict[str, float] = collections.Counter()
    devs = rec["devices"]
    n = max(len(devs), 1)
    op = 0.0
    for dev in devs:
        for name, _, d in dev["ops"]:
            unit, role = scope_of(names.get(name, ""))
            s = d * 1e-9 / n
            op += s
            by_role[role] += s
            by_unit[role][unit] += s
            if role == OTHER:
                other[name] += s
    return Roles(op_s=op, by_role=dict(by_role),
                 by_unit={r: dict(u) for r, u in by_unit.items()},
                 other_ops=[[k, v] for k, v in other.most_common(10)])


def role_table(roles: Roles, calls: int) -> list[str]:
    """One line per role: milliseconds a call, share of device-op time and
    the three units that take most of it."""
    lines = []
    for role in ROLES + (LAYOUT_MIX, MIXED, "head", OTHER):
        s = roles.by_role.get(role, 0.0)
        if s <= 0:
            continue
        top = sorted(roles.by_unit.get(role, {}).items(),
                     key=lambda kv: -kv[1])[:3]
        units = ", ".join(f"{u or '-'} {v / calls * 1e3:.4f}"
                          for u, v in top)
        lines.append(f"{role:<12} {s / calls * 1e3:9.4f} ms/call "
                     f"{roles.share([role]):6.2f}%  top: {units}")
    return lines


# -- host spans and the clock -------------------------------------------------

@dataclasses.dataclass
class Alignment:
    """Host time = device time + ``offset_ns``, known to within the
    interval ``[lo_ns, hi_ns]``."""

    offset_ns: float
    lo_ns: float
    hi_ns: float

    @property
    def width_ns(self) -> float:
        return self.hi_ns - self.lo_ns


def align(applies, syncs, modules) -> Alignment | None:
    """The host − device offset δ from causality over the traced calls.

    ``applies`` and ``syncs`` are the host's ``executor.apply`` and
    ``bench.sync`` spans, ``modules`` the device's programs, each as
    ``[start_ns, dur_ns]``, one per call.  Call ``i``'s program starts no
    earlier than the host enters its ``executor.apply`` and ends no later
    than the host leaves its ``bench.sync``, so δ lies in
    [max_i(apply_start_i − module_start_i), min_i(sync_end_i −
    module_end_i)]; the midpoint is taken.  Calls that do not pair one
    for one, or an empty interval (the clocks drift), give None."""
    applies, syncs, modules = sorted(applies), sorted(syncs), sorted(modules)
    if not modules or not len(applies) == len(syncs) == len(modules):
        return None
    lo = max(a[0] - m[0] for a, m in zip(applies, modules))
    hi = min(s[0] + s[1] - m[0] - m[1] for s, m in zip(syncs, modules))
    if lo > hi:
        return None
    return Alignment(offset_ns=(lo + hi) / 2, lo_ns=lo, hi_ns=hi)


# -- a recorded window as a fixture -------------------------------------------

_KEEP_LINES = ("XLA Ops", "XLA Modules")


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n") + '"'


def to_text_proto(pd) -> str:
    """A trimmed text form of a trace that ``ProfileData.from_text_proto``
    reads back: the device planes' ``XLA Ops`` and ``XLA Modules`` lines
    (op names other than Pallas calls cut to 100 characters) and the host
    spans ``executor.apply``, ``bench.issue``, ``bench.sync`` and
    ``PjitFunction``."""
    out = []
    for pid, plane in enumerate(pd.planes, 1):
        dev = plane.name.startswith("/device:TPU:")
        if not (dev or plane.name.startswith("/host:")):
            continue
        meta: dict[str, int] = {}
        lines = []
        for lid, line in enumerate(plane.lines, 1):
            if dev and line.name not in _KEEP_LINES:
                continue
            evs = [e for e in line.events
                   if dev or e.name in (APPLY, ISSUE, SYNC)
                   or e.name.startswith("PjitFunction")]
            if not evs:
                continue
            t0 = int(min(e.start_ns for e in evs))
            body = []
            for e in evs:
                name = e.name if CUSTOM_CALL in e.name else e.name[:100]
                mid = meta.setdefault(name, len(meta) + 1)
                body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                            f"{round((e.start_ns - t0) * 1e3)} duration_ps: "
                            f"{round(e.duration_ns * 1e3)} }}")
            lines.append(f"  lines {{ id: {lid} name: {_quote(line.name)} "
                         f"timestamp_ns: {t0}\n" + "\n".join(body) + "\n  }")
        if not lines:
            continue
        md = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
              f"{_quote(n)} }} }}" for n, i in meta.items()]
        out.append(f"planes {{ id: {pid} name: {_quote(plane.name)}\n"
                   + "\n".join(lines + md) + "\n}")
    return "\n".join(out) + "\n"


def write_fixture(out_dir: str, name: str, pd, rec: dict, hlo: str,
                  window_s: float, calls: int, kind: str) -> None:
    """``<name>.trace.pbtxt`` (:func:`to_text_proto`) and
    ``<name>.window.json`` (host-clock window, the compiled forward's
    Pallas call lines, the ``op_name`` of each traced op) in ``out_dir``."""
    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, name)
    with open(base + ".trace.pbtxt", "w") as f:
        f.write(to_text_proto(pd))
    seen = {op for d in rec["devices"] for op, _, _ in d["ops"]}
    with open(base + ".window.json", "w") as f:
        json.dump({
            "about": f"{calls} calls of {name} traced on {kind}; window_s is "
                     f"the host clock over the calls, custom_calls the "
                     f"compiled forward's Pallas call lines up to their "
                     f"backend_config, op_names the op_name of each traced "
                     f"op",
            "window_s": window_s, "calls": calls,
            "custom_calls": "\n".join(
                ln.split(", backend_config=")[0] for ln in hlo.splitlines()
                if CUSTOM_CALL in ln),
            "op_names": {k: v for k, v in sorted(op_names(hlo).items())
                         if k in seen}}, f, indent=1)
