"""``repro.runtime`` — plan execution as a subsystem, not a host detail.

The compression pipeline (tables → DP → replace → fine-tune → merge)
produces a *plan*; this package owns everything that happens after the
plan is frozen:

* :mod:`repro.runtime.ir` — a backend-neutral **unit IR**: typed records
  for merged-conv / depthwise-conv / low-rank-residual / attention /
  pool / upsample / sublayer units with explicit strides, activation
  epilogues, and skip wiring.  Both hosts lower plans into the same IR
  (``host.lower_plan(plan, params) → UnitGraph``), replacing the former
  per-host ``cnn.MergedUnit`` list and ``transformer_host`` tuple units.
* :mod:`repro.runtime.executor` — one shared interpreter over a
  ``UnitGraph`` that routes every unit through the public kernel entry
  points (:mod:`repro.kernels`: Pallas on TPU, jnp oracles elsewhere),
  including a KV-cache-aware decode path for serving transformers.
* :mod:`repro.runtime.artifact` — a portable **merged-model artifact**
  (``.npz``: plan JSON + unit-graph spec + merged weights) with atomic
  publish and a content fingerprint, so compression runs once and every
  consumer (serving, benchmarks, fine-tuning) loads the same certified
  object: ``CompressResult.save(path)`` / ``runtime.load(path)``.
* :mod:`repro.runtime.serving` — the jitted serve protocol: chunked
  prefill + ``lax.scan`` greedy decode (:func:`serve_loop`), a
  fixed-slot batched request scheduler (:func:`serve_requests`) that
  runs ragged prompt batches through ONE fused prefill+decode scan, and
  the continuous-batching engine (:class:`ContinuousEngine` /
  :func:`serve_continuous`): per-slot generation state vmapped through a
  jitted multi-slot chunk, mid-stream admission into vacated slots, and
  individual retirement on EOS / budget / deadline / NaN-abort.
* :mod:`repro.runtime.sampler` — DDIM sampling of a time-conditioned
  CNN graph (the DDPM UNet): :class:`Sampler` runs every denoising step
  of a call inside one jitted program.

**The logical-axis contract.**  Artifacts carry their sharding as data:
every unit record (and the graph) ships an ``axes`` map {param keypath →
logical axis names} written by the host at lowering time
(:func:`repro.runtime.ir.annotate_axes`) — 'ffn'/'heads'/'vocab'/'rank'
for transformer units, 'conv_in'/'conv_out' for merged-conv units,
'embed'/'vocab' at graph level.  A consumer resolves the names through a
:class:`repro.sharding.rules.ShardingRules` to place weights
(``runtime.load(path, rules=...)`` device_puts each array straight to
its ``NamedSharding``), and :class:`GraphExecutor` jits prefill/decode
under the mesh with the matching activation and KV-cache ('kv_seq')
constraints.  No rules — or a one-device mesh — runs the identical code
fully replicated; v1 artifacts load with empty annotations and behave
the same way.

**The precision contract.**  Format-v3 artifacts carry per-unit
quantization *as data*: a unit's static record names its mode
(``quant`` ∈ {'none', 'int8', 'w8a8', 'fp8'}), its weights are stored
narrow (``int8`` / ``float8_e4m3fn``), and the symmetric
per-output-channel scales ride as ordinary param arrays (``w_scale`` on
conv units, ``u_scale``/``v_scale`` on low-rank units) with their own
logical-axes annotations — so the fingerprint, sharding, and quarantine
contracts cover them with zero new machinery.  The executor reads the
mode per unit and routes through the same kernel entry points with
``w_scale=…`` (dequant fused into the fp32 accumulator epilogue) and,
for 'w8a8', ``act_quant=…``; fp units in the same graph are untouched,
so mixed-precision graphs need no special casing anywhere downstream
(serving, fine-tuning consumers, benchmarks all just work).  v1/v2
artifacts have no ``quant`` statics and load with the dataclass default
'none' — exactly the fp semantics they were saved with.  The planner
side of the contract (how the DP chooses which units quantize) lives in
:func:`repro.core.tables.quant_sibling_entries`.

**Failure semantics.**  The runtime is the deployment surface, so its
failure contract is explicit:

* ``load`` never runs a questionable model: a torn, corrupt, or
  tampered artifact (fingerprint mismatch) raises
  :class:`ArtifactError` **after quarantining** the bad file to
  ``<path>.corrupt`` — the next load or re-publish of the same path
  starts clean, and the error names the quarantine file and the
  recovery command.  An unsupported format version raises but leaves
  the file in place (it may be valid under other code).
* ``serve_requests`` degrades per-request, never per-process: a slot
  whose logits go non-finite is aborted at that token (other slots of
  the round are bit-untouched), per-request token and wall-clock
  budgets bound runaway work — the wall-clock deadline is enforced per
  decode chunk, not per round — and on a blown deadline the scheduler
  drains cleanly.  The return still unpacks as ``(gen, seconds)``; the
  per-request outcome lives on ``.report`` (:class:`ServeReport`).
* The continuous engine adds the overload contract on top: every
  request ends in exactly one disposition
  (:data:`repro.runtime.serving.DISPOSITIONS` — ``completed`` /
  ``aborted`` / ``shed`` / ``deadline_miss`` / ``unserved``).  The
  admission queue is bounded and **sheds** up front — on overflow, or
  when the deadline-aware shedder predicts (from the EWMA sustained
  decode rate) that a request cannot finish by its deadline — rather
  than admitting work it will half-serve.  A slot that NaN-aborts
  ``slot_nan_limit`` times is quarantined (circuit breaker: the
  poisoned request is reported, never silently re-queued), and
  shutdown **drains**: in-flight requests finish, waiting ones come
  back ``unserved``.  Per-request latency, queue high-water mark, and
  sustained tok/s land on the same :class:`ServeReport`.
* The serving layer fails over across hosts: a worker loss mid-decode
  surfaces as :class:`repro.runtime.serving.WorkerLost`, and
  :func:`serve_with_failover` harvests the finished requests, re-forms
  the engine on the surviving capacity, and replays the in-flight
  requests from their recorded prompts — deterministic decode makes the
  replayed tokens bit-identical, and the :class:`ServeReport` records
  the event (``failovers`` / ``lost_workers`` / ``replayed``) so
  requests never silently vanish.
* Table builds journal their probes and resume bit-identically — that
  half of the contract is documented in :mod:`repro.core.table_cache`;
  the multi-process fan-out and lease/reassignment contract lives in
  :mod:`repro.core.dist_build` and :mod:`repro.launch.distributed`.
"""
from .artifact import (ArtifactError, CompressedArtifact, fingerprint, load,
                       save)
from .executor import (GraphExecutor, cache_shardings, execute,
                       graph_shardings, init_cache, decode_step, jit_apply,
                       make_serve_step, run_units, slot_state)
from .ir import (AttnUnit, ConvUnit, LowRankUnit, PoolUnit, SublayerUnit,
                 UnitGraph, UpsampleUnit, annotate_axes, bind_params,
                 graph_axes, graph_params)
from .sampler import Sampler, ddim_schedule
from .serving import (DISPOSITIONS, ContinuousEngine, ServeOutput,
                      ServeReport, WorkerLost, decode_tok_s,
                      generate_fused, greedy_token, pad_prompts,
                      ragged_prompts, random_prompts, serve_continuous,
                      serve_loop, serve_loop_pertoken, serve_requests,
                      serve_with_failover, stack_cache)

__all__ = [
    "ArtifactError", "CompressedArtifact", "fingerprint", "load", "save",
    "GraphExecutor", "cache_shardings", "execute", "graph_shardings",
    "init_cache", "decode_step", "jit_apply", "make_serve_step",
    "run_units", "slot_state",
    "AttnUnit", "ConvUnit", "LowRankUnit", "PoolUnit", "SublayerUnit",
    "UnitGraph", "UpsampleUnit", "annotate_axes", "bind_params",
    "graph_axes", "graph_params", "Sampler", "ddim_schedule",
    "DISPOSITIONS", "ContinuousEngine", "ServeOutput", "ServeReport",
    "WorkerLost", "decode_tok_s", "generate_fused", "greedy_token",
    "pad_prompts", "ragged_prompts", "random_prompts", "serve_continuous",
    "serve_loop", "serve_loop_pertoken", "serve_requests",
    "serve_with_failover", "stack_cache",
]
