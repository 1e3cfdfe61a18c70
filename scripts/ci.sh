#!/usr/bin/env bash
# Tier-1 CI entry: exactly the command ROADMAP.md pins.
# Optional dev deps (see requirements-dev.txt) are installed best-effort;
# the suite is self-sufficient without them (tests/conftest.py provides a
# hypothesis fallback).
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${REPRO_CI_INSTALL:-0}" == "1" ]] \
        && ! python -c "import hypothesis" 2>/dev/null; then
    pip install -r requirements-dev.txt \
        || echo "ci.sh: install failed, using the in-repo hypothesis fallback"
fi

# REPRO_PYTEST_XDIST=auto (or an int) parallelizes the run via
# pytest-xdist when it is installed -- CI's tier-1 job sets it to keep
# wall-clock flat as the suite grows; unset, the run is serial. -x is
# dropped under xdist (fail-fast and worker scheduling don't compose;
# failures still fail the run).
XDIST="${REPRO_PYTEST_XDIST:-}"
if [[ -n "$XDIST" ]] && python -c "import xdist" 2>/dev/null; then
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m pytest -q -n "$XDIST" "$@"
else
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
fi
