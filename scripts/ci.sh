#!/usr/bin/env bash
# Tier-1 CI: the fast test set (everything not marked `slow`), fail-fast.
# The `slow` marker covers subprocess dry-run compiles and full-length
# simulations (~6 min) that should not gate every iteration; run them with
#   scripts/ci.sh slow        # only the slow set
#   scripts/ci.sh all         # everything
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CI runs on the CPU (Pallas kernels in interpret mode); a TPU is driven
# by chip_smoke.py, never from here.
export JAX_PLATFORMS=cpu

# The exec-layer tests run in their own pytest process with 4 simulated
# host devices so the multi-device sharded dispatch path is exercised on
# CPU (the flag must be set before jax initializes; trace_guard.py forces
# its own copy). The same tests also pass single-device under a plain
# `pytest` run.
exec_tests() {
  XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m pytest -x -q -m "not slow" tests/test_sim_exec.py
}

case "${1:-tier1}" in
  tier1) python scripts/gen_scenario_docs.py --check
         python scripts/gen_golden_traces.py --check
         python scripts/trace_guard.py
         python scripts/fault_guard.py
         exec_tests
         exec python -m pytest -x -q -m "not slow" \
              --ignore=tests/test_sim_exec.py ;;
  slow)  exec python -m pytest -q -m "slow" ;;
  all)   python scripts/gen_scenario_docs.py --check
         python scripts/gen_golden_traces.py --check
         python scripts/trace_guard.py
         python scripts/fault_guard.py
         exec_tests
         exec python -m pytest -x -q --ignore=tests/test_sim_exec.py ;;
  *)     echo "usage: $0 [tier1|slow|all]" >&2; exit 2 ;;
esac
