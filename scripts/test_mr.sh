#!/usr/bin/env bash
#
# Integration test harness — the Python-framework port of the reference's
# main/test-mr.sh (C12 in SURVEY.md §2): fresh sandbox, sequential oracle,
# 1 coordinator + 3 workers under timeouts, merged-sorted output byte-compared
# against the oracle.  Where the reference builds with the Go race detector
# (test-mr.sh:10,19-22), our concurrency check is the differential comparison
# itself plus the unit tests' lock discipline (SURVEY.md §4).
#
# Usage: scripts/test_mr.sh [app] [backend]
#   app: wc (default), grep, indexer, tfidf, crash, tpu_wc, tpu_grep,
#        tpu_indexer
#   backend: host (default) or tpu (worker runs app device kernels; set
#            DSI_JAX_PLATFORM=cpu to exercise the kernels without a chip).
#            tfidf has its own tpu_map, so `test_mr.sh tfidf tpu` is the
#            device run (no separate tpu_tfidf app name).

set -u
APP=${1:-wc}
BACKEND=${2:-host}
REPO=$(cd "$(dirname "$0")/.." && pwd)
PY=${PYTHON:-python3}
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"

# fresh sandbox cwd (test-mr.sh:13-16)
SANDBOX=$(mktemp -d /tmp/dsi-mr-test.XXXXXX)
trap 'rm -rf "$SANDBOX"' EXIT
cd "$SANDBOX"
export DSI_MR_SOCKET="$SANDBOX/mr.sock"

# inputs: generated corpus (reference pg-*.txt are not distributed; SURVEY §7.1)
$PY -c "from dsi_tpu.utils.corpus import ensure_corpus; ensure_corpus('inputs', n_files=6, file_size=300000)"
INPUTS=(inputs/pg-*.txt)

ORACLE_APP=$APP
case "$APP" in
  tpu_wc) ORACLE_APP=wc ;;          # byte-identical final output to wc
  tpu_indexer) ORACLE_APP=indexer ;;
  tpu_grep) ORACLE_APP=grep
            # The reference harness's own pattern (test-mr.sh:47): runs on
            # device via the class kernel (ops/regexk.py).
            export DSI_GREP_PATTERN=${DSI_GREP_PATTERN:-[Tt]he} ;;
esac
WORKER_ARGS=(--backend "$BACKEND")
EXTRA_COORD_ARGS=()
if [ "$APP" = crash ]; then
  ORACLE_APP=nocrash
  EXTRA_COORD_ARGS=(--task-timeout 2.0)
  export DSI_CRASH_EXIT_PROB=0.3 DSI_CRASH_STALL_PROB=0.15 DSI_CRASH_STALL_S=2.5
fi
if [ "$APP" = grep ]; then
  export DSI_GREP_PATTERN='[Tt]he'
fi
if [ "$APP" = tfidf ]; then
  # N (total docs) is job-level config a per-key reduce cannot derive
  # (apps/tfidf.py n_docs_from_env); the harness knows the input count.
  export DSI_TFIDF_NDOCS=${#INPUTS[@]}
fi

# ground truth via the sequential oracle (test-mr.sh:30-31)
$PY -m dsi_tpu.cli.mrsequential "$ORACLE_APP" "${INPUTS[@]}" --out mr-correct.txt || exit 1
sort mr-correct.txt | grep . > mr-correct-sorted.txt

echo "--- starting $APP test"
rm -f mr-out*
timeout -k 2s 180s $PY -m dsi_tpu.cli.mrcoordinator "${EXTRA_COORD_ARGS[@]}" "${INPUTS[@]}" &
COORD=$!
sleep 1  # socket-creation grace (test-mr.sh:39-40)

# Every worker gets the requested backend.  A real-chip run of a device
# backend goes through `mrrun --backend tpu` instead, which gives each chip
# one device worker and runs the rest as host helpers (dsi_tpu/cli/chips.py);
# here `--backend tpu` needs the CPU named (JAX_PLATFORMS=cpu), or it fails
# at worker start when the chip is missing or taken.
for _ in 1 2 3; do
  timeout -k 2s 180s $PY -m dsi_tpu.cli.mrworker "${WORKER_ARGS[@]}" "$APP" &
done

if [ "$APP" = crash ]; then
  # keep respawning workers while the coordinator lives (crashed ones die)
  while kill -0 $COORD 2>/dev/null; do
    N=$(jobs -rp | wc -l)
    if [ "$N" -lt 4 ]; then
      timeout -k 2s 180s $PY -m dsi_tpu.cli.mrworker "${WORKER_ARGS[@]}" "$APP" &
    fi
    sleep 0.5
  done
fi

wait $COORD
wait

sort mr-out* | grep . > mr-all.txt   # test-mr.sh:52
if cmp -s mr-all.txt mr-correct-sorted.txt; then
  echo "--- $APP test: PASS"
  exit 0
else
  echo "--- $APP output is not the same as the sequential oracle"
  echo "--- $APP test: FAIL"
  exit 1
fi
