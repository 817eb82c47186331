# Convenience targets for the CPU; everything pins JAX_PLATFORMS=cpu (see
# scripts/verify.sh for why).  On a TPU machine run `python3 chip_smoke.py`.

PY := python
ENV := JAX_PLATFORMS=cpu PYTHONPATH=src

.PHONY: verify test bench bench-dp bench-tables bench-serve bench-smoke \
	fault-smoke serve-fault-smoke dist-fault-smoke

verify:
	bash scripts/verify.sh

test:
	$(ENV) $(PY) -m pytest -x -q

bench:
	$(ENV) $(PY) -m benchmarks.run

bench-dp:
	$(ENV) $(PY) -m benchmarks.bench_dp

bench-tables:
	$(ENV) $(PY) -m benchmarks.bench_tables

bench-serve:
	$(ENV) $(PY) -m benchmarks.bench_serve

# Seconds-scale regression gates (also part of `make verify`): probe-
# engine parity/accounting + serving-path artifact round-trip, KV-cache
# decode parity, and the sharded executor ≡ single-device gate on 8
# forced host devices — without the slow timing baselines.
bench-smoke:
	$(ENV) $(PY) -m benchmarks.bench_tables --smoke
	$(ENV) $(PY) -m benchmarks.bench_serve --smoke
	$(ENV) $(PY) -m benchmarks.bench_serve --smoke --quantize w8a8
	$(ENV) XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m benchmarks.bench_serve --smoke --mesh --model-par 2

# Crash-safety gate (also part of `make verify`): SIGKILL a journaled
# table build in a child process, resume it, and require the resumed
# tables to be bit-identical to an uninterrupted build.
fault-smoke:
	$(ENV) $(PY) -m repro.testing.faults --smoke

# Overload-safety gate (also part of `make verify`): the continuous
# serve engine under a REPRO_FAULTS delayed-arrival + per-request NaN +
# straggler-chunk spec — dispositions asserted, surviving requests
# bit-identical to the fault-free run.
serve-fault-smoke:
	$(ENV) $(PY) -m repro.testing.faults --serve-smoke

# Distributed-build gate (also part of `make verify`): 2 subprocess
# workers, worker 0 SIGKILLed mid-bucket; a survivor steals the expired
# lease and the merged tables must be bit-identical to a single-process
# build.  Plus the serve-failover replay smoke.
dist-fault-smoke:
	$(ENV) $(PY) -m repro.launch.distributed --fault-smoke
