"""The chip benchmark of this repository.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is started on and
prints one JSON line.  Everything a cell needs is found by name:

* ``bench/configs/<config>.json`` — the network at its published sizes,
  its ``family``, the plan it runs (``<config>.plan.json``), the pinned
  work counts and the correctness limit;
* ``bench/workloads/<traffic>.json`` — the traffic mix (batch, clients,
  input pool, how many answers the check samples);
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric;
* ``bench/<family>.py`` and ``bench/<family>_reference.py`` — how a
  family is driven through the program, and its plain reference.

A family module provides:

* ``init_params(cfg, key)`` and ``make_inputs(cfg, key, pool, batch)`` —
  weights and ``pool`` input batches from a key, on the device, traced
  under one ``jax.jit`` each;
* ``build(cfg, params, plan_text, workdir, clock=None, *, traffic)`` —
  the system under test: an object whose ``apply(x)`` is the entry the
  window drives, run as one program a call (a sampler loops over its
  steps inside it), and whose ``lower(x)`` is that entry's ``jax.jit``
  lowering (the traced window compiles it for its names);
* ``reference(cfg, params, x, plan, passes=None, *, traffic)`` — the
  plain reference's answers for a block of inputs, at the configuration's
  precision, or with ``passes=3`` at the control's (float32 matmuls in
  three bfloat16 passes);
* ``work(cfg, plan)`` — the ``work`` record the configuration file pins:
  ``flops_per_image`` for one forward of the network, and ``units``, one
  per unit in execution order, each with its ``kernel`` (None outside the
  Pallas kernels), ``flops``, ``bytes`` and ``weight_bytes``;
* ``check_config(cfg)`` — raises unless the configuration's layers are
  the program's network and its stored plan is one the program accepts;
* ``forwards(cfg, traffic)``, optional — forwards of the network per
  call (a sampler's steps); 1 where the module has none.  MFU counts
  ``flops_per_image`` that many times, and a kernel's roofline lines the
  first program's Pallas calls up against the plan's kernel units
  repeated that many times.

``traffic`` is the cell's traffic dict.  Besides the fields the generator
reads (``loop``, ``clients``, ``batch``, ``pool``, ``warm_calls``,
``check_calls``, ``ref_block``, ``trace_calls``), a traffic file may hold
fields that only a family reads, such as ``steps`` of a sampling request.

The yardstick (traffic generation, reference, work counts, peaks, trace
reduction, comparison) lives here; from the program the benchmark takes
only the system under test and its names: kernel names, unit and role
scopes of device ops, and the host span ``executor.apply``.
"""
