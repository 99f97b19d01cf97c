#!/bin/bash
#
# Nightly build (analog of ci/nightly-build.sh): premerge + the wider
# lint, fuzz and chaos sweeps + wheel packaging with baked provenance.

set -ex
cd "$(dirname "$0")/.."

ci/premerge.sh

# nightly lint: premerge covers the smoke plans; --full extends the jaxpr
# sync-lint over a chunked join + top-k plan pair
JAX_PLATFORMS=cpu python tools/srjt_lint.py --segments --full \
    --baseline ci/lint-baseline.json

# nightly fuzz sweep: a bigger corpus on a fresh seed over the EXTENDED
# variant matrix (adds dist-nofuse + dist-fused-aqe).  The shrunk repro
# artifact lands in target/fuzz-repro.json on failure — re-run with the
# printed seed to reproduce deterministically.
JAX_PLATFORMS=cpu python tools/srjt_fuzz.py \
    --seed "$(date +%Y%m%d)" --count 150 --full \
    --out target/fuzz-repro.json

# chaos soak: the fault-injection matrix against the pipeline plans
# (docs/ROBUSTNESS.md).  `timeout` is the outermost hang detector — a soak
# that can't finish inside 15 minutes IS a robustness failure.
JAX_PLATFORMS=cpu timeout 900 python ci/chaos_soak.py --devices 2

# wheel with provenance baked in (build/build-info ran in premerge)
python -m pip wheel --no-deps --no-build-isolation -w target/dist . \
    || python -m pip wheel --no-deps -w target/dist .

echo "nightly: OK"
