#!/bin/bash
# Kill-resume smoke (resilience layer): SIGTERM a short training run midway
# and assert, ON THE REAL CHIP, the two halves of the preemption story the
# CPU chaos suite (tests/test_resilience.py) pins functionally:
#   1. the run exits with the distinct preemption code (75, EX_TEMPFAIL)
#      after writing a verifiable emergency checkpoint + a kind="preemption"
#      record;
#   2. a --resume run continues from that checkpoint and completes with
#      exit 0.
# Emits one JSON verdict line on stdout (a caller may append it to its
# job's outfile); any assertion failure exits nonzero, so a
# caller never records a hollow pass.
set -u
WORK=$(mktemp -d /tmp/kill_resume.XXXXXX)
trap 'rm -rf "$WORK"' EXIT
cd "$(dirname "$0")/.."

python - "$WORK" <<'EOF'
import sys
import numpy as np
from pathlib import Path
work = Path(sys.argv[1])
np.tile(np.arange(256, dtype=np.uint16), 2000).tofile(work / "tokens.bin")
EOF

TRAIN=(python -m bpe_transformer_tpu.training.cli train
  --data "$WORK/tokens.bin" --preset ts-test
  --steps 200000 --batch-size 8 --log-every 20 --eval-every 1000000
  --checkpoint-every 1000 --checkpoint-dir "$WORK/ckpt"
  --metrics-jsonl "$WORK/metrics.jsonl" --warmup 5)

"${TRAIN[@]}" > "$WORK/train.log" 2>&1 &
pid=$!
# Wait for a few logged windows so the SIGTERM lands mid-run, post-compile.
for _ in $(seq 1 120); do
  [ -e "$WORK/metrics.jsonl" ] && \
    [ "$(wc -l < "$WORK/metrics.jsonl")" -ge 6 ] && break
  kill -0 "$pid" 2>/dev/null || break
  sleep 1
done
kill -TERM "$pid" 2>/dev/null
wait "$pid"
rc=$?
if [ "$rc" -ne 75 ]; then
  echo "kill_resume: expected preemption exit 75, got $rc" >&2
  tail -5 "$WORK/train.log" >&2
  exit 1
fi
# The emergency checkpoint must verify (jax-free checksum pass).
python -m bpe_transformer_tpu.resilience.integrity "$WORK/ckpt/latest.ckpt" \
  >&2 || exit 1

# Resume to a nearby step and require a clean finish.
stop_step=$(python - "$WORK" <<'EOF'
import json, sys
from pathlib import Path
records = [json.loads(l) for l in (Path(sys.argv[1]) / "metrics.jsonl").open()]
print(next(r["step"] for r in records if r.get("kind") == "preemption"))
EOF
)
resume_steps=$((stop_step + 100))
python -m bpe_transformer_tpu.training.cli train \
  --data "$WORK/tokens.bin" --preset ts-test \
  --steps "$resume_steps" --batch-size 8 --log-every 20 --eval-every 1000000 \
  --checkpoint-every 1000 --checkpoint-dir "$WORK/ckpt" \
  --metrics-jsonl "$WORK/metrics.jsonl" --warmup 5 \
  --resume "$WORK/ckpt" > "$WORK/resume.log" 2>&1
rrc=$?
if [ "$rrc" -ne 0 ]; then
  echo "kill_resume: resume run failed (exit $rrc)" >&2
  tail -5 "$WORK/resume.log" >&2
  exit 1
fi
python - "$WORK" "$stop_step" "$resume_steps" <<'EOF'
import json, sys
from pathlib import Path
work, stop_step, resume_steps = Path(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
records = [json.loads(l) for l in (work / "metrics.jsonl").open()]
steps = [r["step"] for r in records if "kind" not in r and "loss" in r]
assert max(steps) == resume_steps, (max(steps), resume_steps)
print(json.dumps({
    "job": "kill_resume",
    "preempt_exit": 75,
    "stopped_at_step": stop_step,
    "resumed_to_step": resume_steps,
    "recovered": True,
}))
EOF
