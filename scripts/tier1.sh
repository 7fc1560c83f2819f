#!/usr/bin/env bash
# Tier-1 test suite, run the way the driver runs it: ONE pytest run of
# tests/ on six xdist workers, a test file a worker (--dist loadfile),
# under one 1,470 s limit (PR 46, this sandbox: about 470 s of wall and
# 2,180 CPU-seconds; 910 s and 3,583 at the driver before it).
#
# Output contract:
#   the 20 slowest tests (--durations=20)
#   CPU-seconds by test file, the ten largest, and
#   T1_SECONDS: <total> CPU in <n> files, <wall> wall
#                     the sum of each file's testcase times from the
#                     junit report: `--dist loadfile` hands a worker a
#                     FILE, so the largest file is the run's floor (the
#                     rule, ROADMAP's Tier-1 paragraph: none above 200)
#   T1_DIR=<dir>      this run's own directory, a fresh
#                     ${TMPDIR:-/tmp}/t1.XXXXXX, holding t1.log and
#                     t1.xml (the junit report); two checkouts on one
#                     machine never share it
#   DOTS_PASSED=<n>   passed tests, from the junit report; a run that
#                     wrote no report prints DOTS_PASSED=0 and exits
#                     non-zero
#   exit code = pytest's (124: the limit cut the run)
#
# The driver also exports ALLOW_MULTIPLE_LIBTPU_LOAD=1.  This script
# does not: tests/test_tpu_bringup.py is the one file that loads the
# TPU's library, inside a fixture, so one worker loads it.
#
# Usage: scripts/tier1.sh [extra pytest args...]
set -u -o pipefail

cd "$(dirname "$0")/.."

d=$(mktemp -d "${TMPDIR:-/tmp}/t1.XXXXXX") || exit 1
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile --junitxml="$d/t1.xml" \
    -p no:randomly --durations=20 "$@" 2>&1 | tee "$d/t1.log"
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' \
           "$d/t1.xml" 2>/dev/null | head -n 1 \
       | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
# what `--dist loadfile` packs onto the workers is a FILE, so the
# largest file is the run's floor (ROADMAP, Tier-1: none above 200)
python - "$d/t1.xml" <<'PY'
import collections
import sys
import xml.etree.ElementTree as ET

try:
    suite = ET.parse(sys.argv[1]).getroot().find("testsuite")
except (OSError, ET.ParseError):
    sys.exit(0)  # no report: the lines below say so
by_file = collections.Counter()
for case in suite.iter("testcase"):
    # "tests.test_x" (".TestClass" where a file has classes)
    by_file[(case.get("classname", "?").split(".") + ["?"])[1]] += float(
        case.get("time", 0))
print("CPU-seconds by test file, the ten largest:")
for name, seconds in by_file.most_common(10):
    print(f"{seconds:9.1f}  {name}")
print(f"T1_SECONDS: {sum(by_file.values()):.0f} CPU in {len(by_file)} "
      f"files, {float(suite.get('time')):.0f} wall")
PY
echo "T1_DIR=$d"
echo "DOTS_PASSED=${said:-0}"
[ -n "$said" ] || { echo "tier1.sh: no junit report in $d" >&2; [ "$rc" -ne 0 ] || rc=1; }
exit "$rc"
