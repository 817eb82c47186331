"""The chip benchmark of this repository.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is started on and
prints one JSON line.  Everything a cell needs is found by name:

* ``bench/configs/<config>.json`` — the network at its published sizes,
  the plan it runs (``<config>.plan.json``), the pinned work counts and
  the correctness limit;
* ``bench/workloads/<traffic>.json`` — the traffic mix (batch, clients,
  input pool, how many answers the check samples);
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric;
* ``bench/<family>.py`` and ``bench/<family>_reference.py`` — how a
  family is driven through the program, and its plain reference.

The yardstick (traffic generation, reference, work counts, peaks, trace
reduction, comparison) lives here; from the program the benchmark takes
only the system under test.
"""
