#!/usr/bin/env bash
# Tier-1 verify gate — the exact command from ROADMAP.md, reproducible.
#   ./scripts/tier1.sh            # full suite + CLI smoke
#   ./scripts/tier1.sh -m 'not slow'   # quick pass (extra args forwarded)
set -euo pipefail
cd "$(dirname "$0")/.."
# Injected deadlocks in the fault suite must FAIL the gate, not hang it:
# with pytest-timeout installed every test gets a hard cap; without it
# the SIGALRM fallback in tests/conftest.py honours the same `timeout`
# markers (the fault tests all carry one).
TIMEOUT_ARGS=""
if python -c 'import pytest_timeout' 2>/dev/null; then
  TIMEOUT_ARGS="--timeout=120 --timeout-method=thread"
fi
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
  $TIMEOUT_ARGS "$@"
# The serving path (model bank + cell-routed engine), the async/overlap
# serving conformance suite (swap conservation included), the fault
# injection suite (crash-safe checkpoints, wave preemption, hot swap,
# overload shedding), the ChunkSource contract, the streaming pipeline
# (bitwise cell-plan parity, wave training) and the staged
# train->select->test API are part of the default gate: when extra args
# filter the main run, still verify them explicitly (quick hypothesis
# profiles only — the large profiles carry the slow marker).
if [ "$#" -gt 0 ]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
    $TIMEOUT_ARGS -m 'not slow' \
    tests/test_serve_svm.py tests/test_serve_async.py tests/test_faults.py \
    tests/test_sources_contract.py tests/test_pipeline.py \
    tests/test_staged_api.py
fi

# CLI smoke: the staged cycle as three separate processes on tiny synthetic
# data — train writes the surface, select re-picks under NPL, test streams.
SMOKE=$(mktemp -d)
trap 'rm -rf "$SMOKE"' EXIT
PYTHONPATH=src python - "$SMOKE" <<'PY'
import sys
import numpy as np
from repro.data.synthetic import covtype_like, train_test_split
x, y = covtype_like(n=300, d=4, seed=0, label_noise=0.05, n_modes=3)
xtr, ytr, xte, yte = train_test_split(x, np.where(y == 0, -1, 1), 0.25, 0)
d = sys.argv[1]
np.save(f"{d}/xtr.npy", xtr); np.save(f"{d}/ytr.npy", ytr)
np.save(f"{d}/xte.npy", xte); np.save(f"{d}/yte.npy", yte)
PY
PYTHONPATH=src python -m repro.cli train --data "$SMOKE/xtr.npy" \
  --labels "$SMOKE/ytr.npy" --model-dir "$SMOKE/model" --scenario npl \
  -S FOLDS=2 -S MAX_ITERATIONS=150 -S ADAPTIVITY_CONTROL=1 \
  -S WEIGHTS='0.5 1.0 2.0' > /dev/null
PYTHONPATH=src python -m repro.cli select --model-dir "$SMOKE/model" \
  -S NPL_CONSTRAINT=0.05 > /dev/null
PYTHONPATH=src python -m repro.cli test --data "$SMOKE/xte.npy" \
  --labels "$SMOKE/yte.npy" --model-dir "$SMOKE/model"
# serve: cold-start the async engine from bank/ alone, latency-bounded,
# with the hot-swap watcher, a bounded admission queue, the health
# monitor (SLO + drift keys), and the observability keys (tracing +
# metrics/trace export) enabled
PYTHONPATH=src python -m repro.cli serve --data "$SMOKE/xte.npy" \
  --model-dir "$SMOKE/model" --wave 16 -S DEADLINE_MS=5 \
  -S SWAP_POLL_MS=50 -S MAX_QUEUE=4096 --swap-watch \
  -S SLO_P99_MS=500 -S DRIFT_WINDOW=5 -S DRIFT_REFRESH_THRESHOLD=3 \
  -S TRACE=1 -S METRICS_OUT="$SMOKE/metrics.jsonl" \
  -S TRACE_OUT="$SMOKE/trace.jsonl" \
  --out "$SMOKE/pred.npy" > "$SMOKE/serve_out.json"
PYTHONPATH=src python - "$SMOKE" <<'PY'
import sys
import numpy as np
pred = np.load(f"{sys.argv[1]}/pred.npy")
yte = np.load(f"{sys.argv[1]}/yte.npy")
assert pred.shape == yte.shape, (pred.shape, yte.shape)
assert (pred == np.sign(yte)).mean() > 0.5, "serve predictions degenerate"
PY

# metrics-schema smoke: the serve run above exported its registry via
# METRICS_OUT — the JSONL must validate against repro.obs.metrics.v1
# (operator dashboards pin this schema; drift fails the gate here), and
# the serve payload must carry the per-request stage breakdown + trace
PYTHONPATH=src python - "$SMOKE" <<'PY'
import json
import sys
from repro.obs.metrics import MetricsRegistry, validate_jsonl
d = sys.argv[1]
errs = validate_jsonl(f"{d}/metrics.jsonl")
assert errs == [], f"metrics JSONL schema drift: {errs}"
reg, header = MetricsRegistry.read_jsonl(f"{d}/metrics.jsonl")
assert header["stage"] == "serve", header
served = reg.counter("serve.served").value
assert served > 0 and reg.histogram("serve.request_ms").count == served
payload = json.load(open(f"{d}/serve_out.json"))
assert set(payload["per_stage"]) == {"queue", "pack", "dispatch",
                                     "device", "collect"}, payload
assert "serve.pack" in payload["trace"], sorted(payload["trace"])
# health monitor keys attached a HealthMonitor: the payload carries the
# structured verdict (drift baseline recorded at to_bank time, SLO state)
h = payload["health"]
assert h["status"] in ("ok", "degraded", "breaching"), h
assert h["drift"]["baseline"] is True, h
assert "burn_rate" in h["slo"], h
assert "deadline_miss_ratio" in h, h
PY

# trace-schema smoke: TRACE_OUT dumped the retained span window — the
# JSONL must validate against repro.obs.trace.v1 (same contract as the
# metrics schema above: operator tooling pins it, drift fails the gate)
PYTHONPATH=src python - "$SMOKE" <<'PY'
import json
import sys
from repro.obs.trace import validate_trace_jsonl
d = sys.argv[1]
errs = validate_trace_jsonl(f"{d}/trace.jsonl")
assert errs == [], f"trace JSONL schema drift: {errs}"
payload = json.load(open(f"{d}/serve_out.json"))
assert payload["trace_out"] == f"{d}/trace.jsonl", payload.get("trace_out")
PY

# CLI failure modes: missing/incomplete artifacts must exit non-zero with
# an actionable message (which stage to run), never a raw traceback
if PYTHONPATH=src python -m repro.cli select \
    --model-dir "$SMOKE/nomodel" 2> "$SMOKE/err.txt"; then
  echo "tier1: select on a missing model dir must fail"; exit 1
fi
grep -q "missing 'train/'" "$SMOKE/err.txt"
grep -q "repro.cli train" "$SMOKE/err.txt"
if PYTHONPATH=src python -m repro.cli test --data "$SMOKE/xte.npy" \
    --labels "$SMOKE/yte.npy" \
    --model-dir "$SMOKE/nomodel" 2> "$SMOKE/err.txt"; then
  echo "tier1: test on a missing model dir must fail"; exit 1
fi
grep -q "missing 'select/'" "$SMOKE/err.txt"
mkdir -p "$SMOKE/torn/train"           # dir exists but artifact is torn
if PYTHONPATH=src python -m repro.cli select \
    --model-dir "$SMOKE/torn" 2> "$SMOKE/err.txt"; then
  echo "tier1: select on a torn train artifact must fail"; exit 1
fi

echo "tier1: CLI smoke OK"

# embed-cycle smoke: the LM-embedding vertical as separate processes —
# embed materializes the frozen-backbone cache under <model-dir>/embed,
# train/select run over the replayed shards, serve takes raw TOKENS and
# reports the co-located embed->route->blend breakdown (embed stage
# present in per_stage)
PYTHONPATH=src python - "$SMOKE" <<'PY'
import sys
import numpy as np
rng = np.random.default_rng(0)
n = 120
tok = np.concatenate([rng.integers(0, 250, size=(n // 2, 12)),
                      rng.integers(250, 500, size=(n // 2, 12))]
                     ).astype(np.int32)
y = np.repeat([-1.0, 1.0], n // 2)
perm = rng.permutation(n)
d = sys.argv[1]
np.save(f"{d}/tok.npy", tok[perm]); np.save(f"{d}/ytok.npy", y[perm])
PY
PYTHONPATH=src python -m repro.cli embed --tokens "$SMOKE/tok.npy" \
  --model-dir "$SMOKE/emodel" -S EMBED_ARCH=stablelm-1.6b:smoke \
  -S EMBED_BATCH=32 > /dev/null
PYTHONPATH=src python -m repro.cli train --data "$SMOKE/emodel/embed" \
  --labels "$SMOKE/ytok.npy" --model-dir "$SMOKE/emodel" \
  -S FOLDS=2 -S MAX_ITERATIONS=150 > /dev/null
PYTHONPATH=src python -m repro.cli select --model-dir "$SMOKE/emodel" \
  > /dev/null
PYTHONPATH=src python -m repro.cli serve --tokens "$SMOKE/tok.npy" \
  --model-dir "$SMOKE/emodel" --wave 32 > "$SMOKE/embed_serve_out.json"
PYTHONPATH=src python - "$SMOKE" <<'PY'
import json
import sys
payload = json.load(open(f"{sys.argv[1]}/embed_serve_out.json"))
assert set(payload["per_stage"]) == {"queue", "pack", "dispatch", "device",
                                     "collect", "embed"}, payload
assert payload["per_stage"]["embed"]["total_ms"] > 0, payload
PY

echo "tier1: embed cycle OK"

echo "tier1: OK"
