"""Backend-neutral unit IR for merged (compressed) networks.

A :class:`UnitGraph` is the executable form of a compression plan: an
ordered chain of typed *units*, each a record of STATIC configuration
(strides, kernel geometry, activation epilogue, skip wiring) plus a
``params`` pytree of arrays (merged weights).  Hosts lower plans into
this IR (``host.lower_plan(plan, params) → UnitGraph``); the shared
interpreter in :mod:`repro.runtime.executor` runs it; the artifact layer
in :mod:`repro.runtime.artifact` serializes it.

Design rules:

* Static fields are plain JSON-able Python values — they round-trip
  through the artifact spec unchanged.  Arrays live only in ``params``.
* Units never reference host objects (``ConvNet``, ``ArchConfig``
  instances, parameter dicts of the *uncompressed* network): everything
  the executor needs is in the unit record or ``UnitGraph.meta``.
* Skip/branch wiring is expressed through boundary ids: a unit may
  ``save_at`` a boundary and later units may ``add_from`` /
  ``concat_from`` it — the executor keeps the saved-activation table.
* Sharding is DATA, not code: every unit carries an ``axes`` record
  mapping param key-paths to *logical axis names* (MaxText-style, see
  :mod:`repro.sharding.rules`), and ``UnitGraph.axes`` does the same for
  graph-level params.  The executor resolves names → ``NamedSharding``
  through whatever :class:`ShardingRules` it is given; an artifact
  therefore ships its own sharding contract and a loader can
  ``device_put`` weights straight to their mesh placement.  Hosts
  populate the annotations at lowering time via :func:`annotate_axes`;
  empty ``axes`` simply means fully replicated.

CNN unit semantics (epilogue order matches the merged forward that the
merge-equality tests certify): [input group-norm → activation] → conv →
per-image time bias → skip-add → concat → group-norm → boundary
activation → save.  The bracketed input nonlinearity is a
pre-activation net's (DDPM): it feeds the conv only, while skips read
the raw saved boundary values.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping


@dataclasses.dataclass
class ConvUnit:
    """One merged conv segment: VALID conv at the merged kernel size.

    ``params``: ``w`` (Kh,Kw,Cin|1,Cout), ``b`` (Cout,), optional
    ``gn`` {gamma, beta} (group-norm moved to the segment end, paper
    Appendix A), optional ``proj`` {w, b} (1×1 projection shortcut of
    a skip-add ending at this unit's boundary), optional ``pre_gn``
    {gamma, beta} (the input group-norm, with ``gn_groups``/``gn_eps``,
    followed by ``pre_act``, of a pre-activation segment) and optional
    ``temb`` {w (D,Cout), b (Cout,)}: the Dense from the graph's time
    features to the unit's per-image bias, already folded through the
    segment's convs.

    ``quant`` != 'none' marks a low-precision unit (artifact format v3):
    ``w`` is stored narrow (int8 / fp8) and ``params`` carries the
    symmetric per-output-channel ``w_scale`` (Cout,) — scales are DATA,
    serialized alongside the weights like every other array.  'w8a8'
    additionally quantizes the activation per-tensor at run time.
    """

    kind = "conv"
    stride: int = 1
    depthwise: bool = False
    act: str = "none"               # boundary activation σ_j ('none' at σ_L)
    gn_groups: int = 8
    gn_eps: float = 1e-5
    pre_act: str = "none"           # activation after ``pre_gn``
    proj_stride: int = 1
    add_from: int | None = None     # skip-add source boundary id
    concat_from: int | None = None  # U-Net concat source boundary id
    save_at: int | None = None      # boundary id to save the output under
    quant: str = "none"             # 'none' | 'int8' | 'w8a8' | 'fp8'
    axes: dict = dataclasses.field(default_factory=dict)
    params: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class PoolUnit:
    """Average-pool barrier unit (parameter-free)."""

    kind = "pool"
    k: int = 2
    stride: int = 2
    concat_from: int | None = None
    save_at: int | None = None
    axes: dict = dataclasses.field(default_factory=dict)
    params: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class UpsampleUnit:
    """Nearest-neighbour upsample barrier unit (parameter-free)."""

    kind = "upsample"
    factor: int = 2
    concat_from: int | None = None
    save_at: int | None = None
    axes: dict = dataclasses.field(default_factory=dict)
    params: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class AttnUnit:
    """DDPM's self-attention block as a barrier unit: ``x + proj(softmax(
    q kᵀ/√C) v)`` on the group-normed input, one head over H·W positions.

    ``params``: ``gn`` {gamma, beta}, ``wq``/``bq``, ``wk``/``bk``,
    ``wv``/``bv``, ``wo``/``bo`` — passed through unmerged (attention is
    never linearizable).
    """

    kind = "attn"
    gn_groups: int = 32
    gn_eps: float = 1e-6
    concat_from: int | None = None
    save_at: int | None = None
    axes: dict = dataclasses.field(default_factory=dict)
    params: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class LowRankUnit:
    """Rank-``r`` residual map ``x + (x·U)·V`` — a merged FFN segment.

    ``params``: ``u`` (D,r), ``v`` (r,D).  Runs through the Pallas
    ``merged_ffn`` kernel on TPU.  ``quant`` != 'none' (artifact v3):
    ``u``/``v`` stored narrow plus per-output-channel ``u_scale`` (r,)
    and ``v_scale`` (D,); 'w8a8' also quantizes the activation feeding
    the two dots (the residual always adds the exact fp input).
    """

    kind = "lowrank"
    quant: str = "none"             # 'none' | 'int8' | 'w8a8' | 'fp8'
    axes: dict = dataclasses.field(default_factory=dict)
    params: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SublayerUnit:
    """One kept transformer sublayer: pre-norm → block → residual add.

    ``sub_kind``: 'attn' | 'attn_local' | 'ffn' | 'moe' | 'rglru' |
    'mlstm' | 'slstm'.  ``params``: {'norm': rmsnorm scale, 'p': the
    block's parameter pytree}.  Temporal kinds carry decode state (KV
    cache / recurrent state) in the executor's serve path.
    """

    kind = "sublayer"
    sub_kind: str = "ffn"
    axes: dict = dataclasses.field(default_factory=dict)
    params: dict = dataclasses.field(default_factory=dict)


UNIT_TYPES = {
    "conv": ConvUnit,
    "pool": PoolUnit,
    "upsample": UpsampleUnit,
    "attn": AttnUnit,
    "lowrank": LowRankUnit,
    "sublayer": SublayerUnit,
}

#: temporal sublayer kinds that carry decode state in the serve path
TEMPORAL_KINDS = ("attn", "attn_local", "rglru", "mlstm", "slstm")


@dataclasses.dataclass
class UnitGraph:
    """Executable form of a plan: ordered units + graph-level params.

    ``family``: 'cnn' | 'transformer' — selects the executor loop.

    ``params`` (graph-level, outside any unit):
      cnn          — optional ``head`` {w, b} (classifier), optional
                     ``temb`` {w0, b0, w1, b1} (time MLP);
      transformer  — optional ``embed``, ``final_norm``, optional
                     ``unembed``.

    ``meta`` (static):
      cnn          — ``save_input`` (bool: boundary 0 feeds a skip),
                     ``head`` ('classifier' | 'none'), ``temb_dim``
                     (sinusoidal width; present iff time-conditioned:
                     the graph then takes ``(x, t)``);
      transformer  — ``config`` (the :class:`ArchConfig`; serialized as
                     a plain dict in the artifact spec).
    """

    family: str
    units: tuple
    params: dict = dataclasses.field(default_factory=dict)
    meta: dict = dataclasses.field(default_factory=dict)
    #: logical axes of graph-level params (flat keypath → name list);
    #: same contract as the per-unit ``axes`` records
    axes: dict = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# Static spec <-> unit records (artifact serialization support)
# ---------------------------------------------------------------------------

def unit_static(unit) -> dict:
    """JSON-able static record of one unit (everything but ``params``)."""
    out = {"kind": unit.kind}
    for f in dataclasses.fields(unit):
        if f.name == "params":
            continue
        out[f.name] = getattr(unit, f.name)
    return out


def unit_from_static(static: dict, params: dict):
    cls = UNIT_TYPES[static["kind"]]
    kwargs = {k: v for k, v in static.items() if k != "kind"}
    return cls(params=params, **kwargs)


# ---------------------------------------------------------------------------
# Params as a pytree (jit / fine-tune / checkpoint support)
# ---------------------------------------------------------------------------

def graph_params(graph: UnitGraph) -> dict:
    """The graph's arrays as one pytree: {'units': [...], 'globals': {...}}."""
    return {"units": [u.params for u in graph.units],
            "globals": graph.params}


def bind_params(graph: UnitGraph, params: dict) -> UnitGraph:
    """A structurally-identical graph with its arrays replaced.

    ``params`` must match :func:`graph_params` of the same graph — this
    is how the executor exposes a pure ``fn(params, x)`` signature while
    unit records stay the single source of static truth.
    """
    units = tuple(dataclasses.replace(u, params=p)
                  for u, p in zip(graph.units, params["units"]))
    return UnitGraph(family=graph.family, units=units,
                     params=params["globals"], meta=graph.meta,
                     axes=graph.axes)


def count_units(graph: UnitGraph) -> dict[str, int]:
    """Unit census (for benchmarks / reports): kind → count."""
    out: dict[str, int] = {}
    for u in graph.units:
        key = u.kind if u.kind != "sublayer" else f"sublayer:{u.sub_kind}"
        out[key] = out.get(key, 0) + 1
    return out


# ---------------------------------------------------------------------------
# Logical-axis annotations (the artifact sharding contract)
# ---------------------------------------------------------------------------
#
# ``axes`` records are flat dicts {param keypath → [logical names]} — the
# keypath uses '/'-joined keys exactly like the artifact array layout, and
# a name entry of ``None`` (JSON null) means "this dim is never sharded".
# Key-paths absent from the record resolve to fully replicated, so partial
# annotations (and the empty v1-artifact record) are always valid.

def axes_tree(params, flat_axes: Mapping, prefix: str = ""):
    """Axes pytree aligned leaf-for-leaf with ``params``.

    Each array leaf becomes a tuple of logical names (or ``None`` for
    replicated) looked up by its '/'-joined keypath — the shape
    :func:`repro.sharding.rules.param_shardings_with_shapes` consumes.
    """
    if isinstance(params, dict):
        return {k: axes_tree(v, flat_axes, f"{prefix}{k}/")
                for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        return [axes_tree(v, flat_axes, f"{prefix}{i}/")
                for i, v in enumerate(params)]
    names = flat_axes.get(prefix[:-1])
    return tuple(names) if names else None


def unit_axes(unit):
    """Logical-axes pytree matching ``unit.params``."""
    return axes_tree(unit.params, unit.axes)


def graph_axes(graph: UnitGraph) -> dict:
    """Logical-axes pytree matching :func:`graph_params`."""
    return {"units": [unit_axes(u) for u in graph.units],
            "globals": axes_tree(graph.params, graph.axes)}


def _flat_names(tree, prefix: str = "") -> dict:
    """Flatten a nested {key: names-tuple} tree to the flat-dict form."""
    out: dict = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat_names(v, f"{prefix}{k}/"))
        elif v:
            out[f"{prefix}{k}"] = list(v)
    return out


# channels of a merged conv play the role the ffn dim plays in a
# transformer: the model-parallel axis of the unit graph
_CONV_W = [None, None, "conv_in", "conv_out"]
_CONV_W_DW = [None, None, None, "conv_out"]        # (K,K,1,C) depthwise


def _conv_axes(u) -> dict:
    ax = {"w": list(_CONV_W_DW if u.depthwise else _CONV_W),
          "b": ["conv_out"]}
    if "w_scale" in u.params:
        ax["w_scale"] = ["conv_out"]
    if "gn" in u.params:
        ax["gn/gamma"] = ["conv_out"]
        ax["gn/beta"] = ["conv_out"]
    if "proj" in u.params:
        ax["proj/w"] = list(_CONV_W)
        ax["proj/b"] = ["conv_out"]
    return ax


def _sublayer_axes(u, cfg) -> dict:
    from repro.models import layers as L
    from repro.models import moe as MOE
    from repro.models import rglru as RG
    from repro.models import xlstm as XL

    kind = u.sub_kind
    if kind in ("attn", "attn_local"):
        block = L.attention_axes(cfg)
    elif kind == "ffn":
        block = L.ffn_axes(cfg.ffn_kind)
    elif kind == "moe":
        block = MOE.moe_axes()
    elif kind == "rglru":
        block = RG.rglru_axes()
    elif kind == "mlstm":
        block = XL.mlstm_axes()
    elif kind == "slstm":
        block = XL.slstm_axes()
    else:
        block = {}
    ax = {"norm": ["embed"]}
    ax.update(_flat_names({"p": block}))
    return ax


def default_unit_axes(unit, cfg=None) -> dict:
    """The canonical logical-axes record for one unit.

    ``cfg`` (the transformer :class:`ArchConfig`) is required only for
    sublayer units — their block axes come from the model's own axes
    functions, so the artifact contract never drifts from the training
    annotations.
    """
    if unit.kind == "conv":
        return _conv_axes(unit)
    if unit.kind == "attn":
        return {k: ["conv_in", "conv_out"] for k in ("wq", "wk", "wv", "wo")
                if k in unit.params}
    if unit.kind == "lowrank":
        ax = {"u": ["embed", "rank"], "v": ["rank", "embed"]}
        if "u_scale" in unit.params:
            ax["u_scale"] = ["rank"]
        if "v_scale" in unit.params:
            ax["v_scale"] = ["embed"]
        return ax
    if unit.kind == "sublayer":
        return _sublayer_axes(unit, cfg)
    return {}


def graph_global_axes(graph: UnitGraph) -> dict:
    """Canonical logical-axes record for the graph-level params."""
    out: dict = {}
    if graph.family == "transformer":
        if "embed" in graph.params:
            out["embed"] = ["vocab", "embed"]
        out["final_norm"] = ["embed"]
        if "unembed" in graph.params:
            out["unembed"] = ["embed", "vocab"]
    elif "head" in graph.params:
        out["head/w"] = ["conv_in", "vocab"]
        out["head/b"] = ["vocab"]
    return out


def annotate_axes(graph: UnitGraph) -> UnitGraph:
    """Fill in the canonical axes records on a freshly-lowered graph.

    Units that already carry annotations (e.g. loaded from an artifact)
    are left untouched — the artifact's recorded contract wins.  Mutates
    the unit records in place and returns ``graph`` for chaining.
    """
    cfg = graph.meta.get("config")
    for u in graph.units:
        if not u.axes:
            u.axes = default_unit_axes(u, cfg)
    if not graph.axes:
        graph.axes = graph_global_axes(graph)
    return graph
