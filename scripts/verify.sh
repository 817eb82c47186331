#!/usr/bin/env bash
# Tier-1 verification + kernel equivalence, platform-pinned.
#
#   bash scripts/verify.sh [extra pytest args]
#   make verify
#
# JAX_PLATFORMS=cpu is pinned because libtpu is installed and an unpinned
# JAX goes looking for a TPU.  Every test here runs on the CPU: the ops
# take their jnp oracles, kernel tests run Pallas in interpret mode, and
# tests/test_tpu_compile.py compiles for a described v5e without a chip.
# On a TPU machine, `python3 chip_smoke.py` is the end-to-end check.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Kernel equivalence first: the fast, specific signal when iterating on
# Pallas code; the tier-1 pass below skips these files so nothing runs
# twice and the union still covers the whole suite.
KERNEL_SUITE="tests/test_kernels.py tests/test_merged_conv_general.py \
    tests/test_depthwise_conv.py tests/test_fastpath.py \
    tests/test_quant_kernels.py"

echo "== interpret-mode kernel equivalence (Pallas vs jnp oracles) =="
python -m pytest -q $KERNEL_SUITE

echo "== tier-1 suite (remainder) =="
IGNORES=""
for f in $KERNEL_SUITE; do IGNORES="$IGNORES --ignore=$f"; done
python -m pytest -x -q $IGNORES "$@"

echo "== probe-engine bench smoke (table-build parity + accounting) =="
# --workers 0: the dist-fault-smoke leg below covers the fan-out path.
python -m benchmarks.bench_tables --smoke --workers 0 > /dev/null

echo "== serve bench smoke (artifact round-trip + KV-cache parity) =="
python -m benchmarks.bench_serve --smoke > /dev/null

echo "== quantized serve smoke (DP-planned w8a8 leg, >=2x weight bytes) =="
python -m benchmarks.bench_serve --smoke --quantize w8a8 > /dev/null

echo "== serve bench smoke, sharded (forced host devices, data x model) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m benchmarks.bench_serve --smoke --mesh --model-par 2 > /dev/null

echo "== fault-injection smoke (SIGKILL mid-build, resume bit-identical) =="
python -m repro.testing.faults --smoke > /dev/null

echo "== serve fault smoke (continuous engine: NaN + straggler, exact) =="
python -m repro.testing.faults --serve-smoke > /dev/null

echo "== distributed fault smoke (worker SIGKILL -> lease reassignment; =="
echo "==   serve failover replay, zero lost requests, bit-identical)    =="
python -m repro.launch.distributed --fault-smoke > /dev/null

echo "verify: OK"
