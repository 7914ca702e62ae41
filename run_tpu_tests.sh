#!/bin/bash
# Run the hardware-gated TPU suite on a machine with a chip and keep the
# output. One process per chip: nothing else that uses jax may run there.
set -o pipefail
out="${1:-chiprun_out/TPU_TESTS_$(date +%Y%m%d).txt}"
mkdir -p "$(dirname "$out")"
PIXIE_TPU_RUN_TPU_TESTS=1 python -m pytest tests/test_tpu.py -v -s 2>&1 | tee "$out"
