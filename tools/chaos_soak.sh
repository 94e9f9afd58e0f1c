#!/bin/bash
# Repeated seeded chaos soak.
#
# Loops the slow chaos suites — the multi-seed delay/reorder bit-exact
# soak and the low-rate corruption soak — across a sweep of seeds fed
# in via MPI_TPU_CHAOS-style specs, logging one line per iteration to
# CHAOS_SOAK_LOG.md. Every fault decision is a pure function of the
# seed (mpi_tpu/chaos.py), so any failure line is an exact repro
# recipe: rerun with the printed seed.
#
# Usage:
#   tools/chaos_soak.sh            # default 10 iterations
#   tools/chaos_soak.sh 100        # longer soak
#   SEED_BASE=500 tools/chaos_soak.sh
cd "$(dirname "$0")/.." || exit 1

ITERS="${1:-10}"
SEED_BASE="${SEED_BASE:-0}"
LOG=CHAOS_SOAK_LOG.md
# Flight-recorder dumps (docs/OBSERVABILITY.md): every chaos-killed or
# deadline-failed rank in the soak leaves its postmortem here, so a
# failing seed ships with a "what was each rank doing" snapshot. The
# nightly job archives this directory as a build artifact.
PM_DIR="${MPI_TPU_POSTMORTEM_DIR:-chaos-postmortems}"
mkdir -p "$PM_DIR"
export MPI_TPU_POSTMORTEM_DIR="$(cd "$PM_DIR" && pwd)"

echo "- $(date -u '+%Y-%m-%d %H:%M UTC'): soak start iters=$ITERS seed_base=$SEED_BASE" >> "$LOG"

fails=0
for i in $(seq 1 "$ITERS"); do
  seed=$((SEED_BASE + i))
  # Yield to a foreign bench run: chaos delay timing plus a contended
  # core makes spurious slowness, not signal.
  while pgrep -f "python[^ ]* ([^ ]*/)?bench\.py" > /dev/null 2>&1; do
    sleep 60
  done
  if JAX_PLATFORMS=cpu MPI_TPU_CHAOS_SOAK_SEED="$seed" timeout 900 \
      python -m pytest tests/test_chaos.py -q -m slow \
      -p no:cacheprovider > /tmp/chaos_soak_run.log 2>&1; then
    echo "- $(date -u '+%Y-%m-%d %H:%M UTC'): seed $seed OK" >> "$LOG"
  else
    fails=$((fails + 1))
    tail -5 /tmp/chaos_soak_run.log | sed 's/^/    /' >> "$LOG"
    echo "- $(date -u '+%Y-%m-%d %H:%M UTC'): seed $seed FAIL (log above)" >> "$LOG"
  fi
  # Crash drive: one seeded rank-death under the real launcher per
  # iteration — the in-process slow suites never kill a rank, so this
  # is what actually exercises the flight-recorder dump + job-report
  # path and fills the archived postmortem dir. Expected exit: the
  # chaos crash code (37); anything else (including success) is a
  # soak failure.
  crash_prog=$(mktemp /tmp/chaos_soak_crash_XXXX.py)
  cat > "$crash_prog" <<'PYEOF'
import sys
import mpi_tpu
mpi_tpu.init()
r, n = mpi_tpu.rank(), mpi_tpu.size()
for step in range(200):
    mpi_tpu.sendrecv(r, dest=(r + 1) % n, source=(r - 1) % n, tag=step)
mpi_tpu.finalize()
sys.exit(0)
PYEOF
  port=$((21000 + (seed % 500) * 4))
  JAX_PLATFORMS=cpu timeout 120 python -m mpi_tpu.launch.mpirun \
      --port-base "$port" --timeout 30 --postmortem-dir "$MPI_TPU_POSTMORTEM_DIR" \
      --chaos "$seed:1:crash@6" 2 "$crash_prog" \
      > /tmp/chaos_soak_crash.log 2>&1
  crash_rc=$?
  rm -f "$crash_prog"
  if [ "$crash_rc" -eq 37 ] && \
      grep -q "last in-flight op" /tmp/chaos_soak_crash.log; then
    echo "- $(date -u '+%Y-%m-%d %H:%M UTC'): seed $seed crash-drive OK (job postmortem collected)" >> "$LOG"
  else
    fails=$((fails + 1))
    tail -5 /tmp/chaos_soak_crash.log | sed 's/^/    /' >> "$LOG"
    echo "- $(date -u '+%Y-%m-%d %H:%M UTC'): seed $seed crash-drive FAIL rc=$crash_rc" >> "$LOG"
  fi
done

dumps=$(ls "$MPI_TPU_POSTMORTEM_DIR"/postmortem-*.json 2>/dev/null | wc -l)
echo "- $(date -u '+%Y-%m-%d %H:%M UTC'): soak done, $fails/$ITERS failed, $dumps flight-recorder dump(s) in $MPI_TPU_POSTMORTEM_DIR" >> "$LOG"
exit "$((fails > 0))"
