"""Portable merged-model artifacts — compress once, deploy everywhere.

An artifact is ONE ``.npz`` file holding everything a consumer needs to
run a compressed network without re-running the pipeline:

* ``__spec__``  — JSON: format version, graph family, static unit
  records (:func:`repro.runtime.ir.unit_static`), graph meta (the
  transformer ``ArchConfig`` as a plain dict), the compression plan
  (``CompressionPlan.to_json`` payload), and caller metadata (which
  latency oracle certified the plan, measured latencies, source seed);
* ``u<i>/<keypath>`` — the merged weights of unit ``i``, flattened by
  key-path exactly like :mod:`repro.checkpoint.ckpt`;
* ``g/<keypath>`` — graph-level params (embed / final norm / unembed /
  classifier head / a time-conditioned CNN's time MLP ``temb``, whose
  embedding width rides in the graph meta as ``temb_dim``);
* ``__fingerprint__`` — sha256 over the canonical spec JSON plus every
  array's key, dtype, shape, and raw bytes (the same content-hash style
  as :func:`repro.core.table_cache.pytree_digest`).

Publish is atomic (write ``path + '.tmp'``, then rename — the
checkpoint/table-cache crash contract), and :func:`load` re-verifies the
fingerprint, so a reader never observes a torn or bit-rotted artifact as
valid: corruption raises :class:`ArtifactError` instead of mis-parsing.

Format v2 adds the sharding contract: each unit static carries its
``axes`` record and the spec carries ``global_axes`` (logical axis names
per param keypath, see :mod:`repro.runtime.ir`).  ``load(path, rules=)``
resolves those names through a :class:`ShardingRules` and ``device_put``s
every array STRAIGHT to its ``NamedSharding`` — no replicated host-side
copy is materialized on the devices first.  v1 artifacts (no
annotations) still load, as fully replicated graphs.

Format v3 adds per-unit precision: a quantized unit's static record
carries ``quant`` ('int8' | 'w8a8' | 'fp8'), its weights are stored
narrow, and its symmetric per-output-channel scales travel as ordinary
param arrays (``w_scale`` / ``u_scale`` / ``v_scale``) with their own
logical-axes annotations — no side-channel blobs, so the fingerprint,
sharding, and crash contracts cover them unchanged.  v1/v2 artifacts
(no ``quant`` field) still load: the unit dataclass default 'none' is
exactly the fp semantics they were saved with.

A time-conditioned CNN (the DDPM UNet) needs no new format: its units'
input norms (``pre_gn``), time-bias Dense (``temb``) and attention
weights are ordinary param arrays, and a conv unit's new static fields
(``pre_act``, ``gn_eps``) default to what older artifacts meant.  An
attention unit is now DDPM's block (GroupNorm, biased projections); an
artifact holding the former bare attention unit does not run.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import zipfile
from typing import Any

import jax
import numpy as np

from . import ir

FORMAT_VERSION = 3
SUPPORTED_FORMATS = (1, 2, 3)


class ArtifactError(RuntimeError):
    """Raised when an artifact is missing, torn, corrupt, or stale."""


# ---------------------------------------------------------------------------
# Pytree <-> flat key-path arrays
# ---------------------------------------------------------------------------

def _flatten(tree) -> dict[str, np.ndarray]:
    from repro.checkpoint.ckpt import flatten_leaves
    return flatten_leaves(tree)


def _listify(node):
    if not isinstance(node, dict):
        return node
    node = {k: _listify(v) for k, v in node.items()}
    if node and all(k.isdigit() for k in node):
        return [node[str(i)] for i in range(len(node))]
    return node


def _unflatten(flat: dict[str, Any]):
    """Rebuild the nested pytree from key-paths (digit components are
    list indices — parameter dict keys are never all-digit strings)."""
    root: dict = {}
    for key, val in flat.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return _listify(root)


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------

def _meta_to_spec(meta: dict) -> dict:
    out = dict(meta)
    cfg = out.get("config")
    if cfg is not None and dataclasses.is_dataclass(cfg):
        out["config"] = dataclasses.asdict(cfg)
    return out


def _meta_from_spec(spec_meta: dict) -> dict:
    out = dict(spec_meta)
    if "config" in out and isinstance(out["config"], dict):
        from repro.configs.base import ArchConfig

        d = dict(out["config"])
        d["temporal_pattern"] = tuple(d.get("temporal_pattern", ("attn",)))
        out["config"] = ArchConfig(**d)
    return out


def _payload(graph: ir.UnitGraph, plan=None, meta: dict | None = None):
    spec = {
        "format": FORMAT_VERSION,
        "family": graph.family,
        "graph_meta": _meta_to_spec(graph.meta),
        "global_axes": graph.axes,
        "meta": meta or {},
        "plan": json.loads(plan.to_json()) if plan is not None else None,
        "units": [ir.unit_static(u) for u in graph.units],
    }
    arrays: dict[str, np.ndarray] = {}
    for i, u in enumerate(graph.units):
        for k, v in _flatten(u.params).items():
            arrays[f"u{i:04d}/{k}"] = v
    for k, v in _flatten(graph.params).items():
        arrays[f"g/{k}"] = v
    return spec, arrays


def _digest(spec: dict, arrays: dict[str, np.ndarray]) -> str:
    h = hashlib.sha256()
    h.update(json.dumps(spec, sort_keys=True).encode())
    for key in sorted(arrays):
        arr = arrays[key]
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def fingerprint(graph: ir.UnitGraph, plan=None, meta: dict | None = None
                ) -> str:
    """Content fingerprint of the artifact ``save`` would publish."""
    spec, arrays = _payload(graph, plan, meta)
    return _digest(spec, arrays)


# ---------------------------------------------------------------------------
# Save / load
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompressedArtifact:
    """A loaded merged-model artifact: the certified deployable object."""

    graph: ir.UnitGraph
    plan: Any                        # CompressionPlan | None
    fingerprint: str
    meta: dict                       # caller metadata recorded at save time
    path: str = ""

    def apply(self, inputs):
        """Forward pass (CNN image batch / transformer prefill batch)."""
        from . import executor
        return executor.execute(self.graph, inputs)

    def make_serve_step(self):
        """Jittable one-token decode step (transformer family)."""
        from . import executor
        return executor.make_serve_step(self.graph)

    def init_cache(self, batch_size: int, seq_len: int):
        from . import executor
        return executor.init_cache(self.graph, batch_size, seq_len)

    def executor(self, rules=None):
        """Mesh-aware jitted executor (see :class:`GraphExecutor`);
        pass the same ``rules`` the artifact was loaded with."""
        from . import executor
        return executor.GraphExecutor(self.graph, rules)


def save(path: str, graph: ir.UnitGraph, plan=None,
         meta: dict | None = None) -> str:
    """Atomically publish ``graph`` (+ plan + metadata) to ``path``.

    Returns the content fingerprint.  A crash mid-write leaves only a
    ``path + '.tmp'`` orphan, never a half-written artifact.  In a
    multi-process run only the main process writes the file
    (:func:`repro.launch.distributed.is_main` — the at-most-once publish
    contract); non-main processes still compute and return the
    fingerprint, so every process agrees on the artifact identity.
    """
    from repro.checkpoint.ckpt import atomic_writer
    from repro.launch.distributed import is_main

    spec, arrays = _payload(graph, plan, meta)
    fp = _digest(spec, arrays)
    if not is_main():
        return fp
    with atomic_writer(path) as f:
        np.savez(f, __spec__=np.array(json.dumps(spec)),
                 __fingerprint__=np.array(fp), **arrays)
    return fp


def _key_axes(spec: dict, key: str):
    """Recorded logical names of one array key ('u<i>/…' or 'g/…')."""
    if key.startswith("g/"):
        return spec.get("global_axes", {}).get(key[2:])
    idx, sub = key.split("/", 1)
    return spec["units"][int(idx[1:])].get("axes", {}).get(sub)


def _corrupt(path: str, msg: str) -> ArtifactError:
    """Quarantine a provably-corrupt artifact and build the error.

    The bad file is renamed to ``<path>.corrupt`` (the table-cache
    quarantine contract) so the next deploy/publish to the same path
    starts clean instead of tripping over the same bytes forever; the
    raised error names the quarantine destination and the recovery path.
    """
    from repro.core.table_cache import quarantine

    dst = quarantine(path)
    where = f" (quarantined to {dst})" if dst else ""
    return ArtifactError(
        f"{msg}{where}; re-publish with repro.runtime.save(...) or "
        "CompressResult.save(...)")


def load(path: str, rules=None) -> CompressedArtifact:
    """Load + verify an artifact; raises :class:`ArtifactError` when the
    file is missing, torn, corrupt, or from an unknown format version.

    Self-healing: a torn/corrupt/tampered file is **quarantined** —
    renamed to ``<path>.corrupt`` — before the error is raised, so the
    bad bytes cannot wedge every subsequent load or block a re-publish
    to the same path (the error message names the quarantine file and
    the recovery command).  A file from an *unsupported format version*
    is left in place — it may be valid under a different code version.

    With ``rules`` (a :class:`ShardingRules` over a live mesh), every
    array is ``device_put`` DIRECTLY to the ``NamedSharding`` its
    recorded logical axes resolve to — each device receives only its
    shard, instead of a replicated host copy being committed first.
    v1 artifacts carry no annotations and load fully replicated.
    """
    if not os.path.exists(path):
        raise ArtifactError(f"no artifact at {path}")
    try:
        with np.load(path, allow_pickle=False) as z:
            data = {k: z[k] for k in z.files}
    except (OSError, ValueError, zipfile.BadZipFile, KeyError) as e:
        raise _corrupt(path,
                       f"torn or unreadable artifact {path}: {e}") from e
    try:
        spec = json.loads(data.pop("__spec__").item())
        stored_fp = data.pop("__fingerprint__").item()
    except (KeyError, json.JSONDecodeError, ValueError) as e:
        raise _corrupt(path,
                       f"artifact {path} has no valid spec: {e}") from e
    if spec.get("format") not in SUPPORTED_FORMATS:
        raise ArtifactError(
            f"artifact {path} format {spec.get('format')!r} not in "
            f"{SUPPORTED_FORMATS}")
    if _digest(spec, data) != stored_fp:
        raise _corrupt(
            path, f"artifact {path} failed fingerprint verification "
            "(corrupt weights or tampered spec)")

    sharded = rules is not None and rules.mesh is not None
    unit_arrays: list[dict] = [{} for _ in spec["units"]]
    global_arrays: dict = {}
    for key, arr in data.items():
        if sharded:
            names = tuple(_key_axes(spec, key) or ())
            val = jax.device_put(arr, rules.named(names, arr.shape))
        else:
            val = jax.numpy.asarray(arr)
        if key.startswith("g/"):
            global_arrays[key[2:]] = val
        else:
            idx, sub = key.split("/", 1)
            unit_arrays[int(idx[1:])][sub] = val
    units = tuple(
        ir.unit_from_static(static, _unflatten(flat))
        for static, flat in zip(spec["units"], unit_arrays))
    graph = ir.UnitGraph(family=spec["family"], units=units,
                         params=_unflatten(global_arrays),
                         meta=_meta_from_spec(spec["graph_meta"]),
                         axes=spec.get("global_axes", {}))
    plan = None
    if spec.get("plan") is not None:
        from repro.core.plan import CompressionPlan
        plan = CompressionPlan.from_json(json.dumps(spec["plan"]))
    return CompressedArtifact(graph=graph, plan=plan, fingerprint=stored_fp,
                              meta=spec.get("meta", {}), path=path)
