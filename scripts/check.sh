#!/usr/bin/env bash
# Tier-1 repo check: docs link integrity + the tier-1 test suite
# (ROADMAP.md's verify command). Usage: scripts/check.sh [pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

# single EXIT trap over an accumulating list: `trap ... EXIT` overwrites
# any previous handler, so steps register cleanups here instead of
# installing their own trap (which would silently leak earlier tempdirs)
CLEANUPS=()
run_cleanups() {
  local d
  for d in ${CLEANUPS[@]+"${CLEANUPS[@]}"}; do rm -rf "$d"; done
}
trap run_cleanups EXIT

echo "== tracked-bytecode gate (no committed __pycache__/*.pyc) =="
if git ls-files | grep -q '\.pyc$'; then
  echo "FAIL: tracked .pyc files:"
  git ls-files | grep '\.pyc$'
  exit 1
fi

echo "== docs link check (DESIGN.md §N references) =="
python scripts/check_docs_links.py

echo "== static analysis (AST lint rules + compile-time plan verifier) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis

# the full tier-1 run already collects the parity + graph + shard suites;
# run them as their own step only when pytest args narrow the tier-1
# selection below
if [ "$#" -gt 0 ]; then
  echo "== op-registry parity + graph-compiler + sharded-plan suites =="
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q tests/test_ops_registry.py tests/test_graph.py tests/test_shard_plan.py
fi

echo "== pipeline_sweep smoke (fused plan vs layer-by-layer) =="
PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.pipeline_sweep --smoke --no-json

echo "== tuning-cache persistence smoke (write in one process, load+use in a fresh one) =="
TUNE_TMP="$(mktemp -d)"
CLEANUPS+=("$TUNE_TMP")
PYTHONPATH=src python - "$TUNE_TMP/cache.json" <<'PY'
import sys
import jax
import repro.ops.autotune as at
at.TUNE_WARMUP, at.TUNE_ITERS = 1, 1          # smoke: one timed launch
from repro.ops import ExecPolicy, TUNING_CACHE, ensure_tuned
x = jax.random.normal(jax.random.PRNGKey(0), (4, 1, 28, 28))
w = jax.random.normal(jax.random.PRNGKey(1), (15, 1, 3, 3))
ensure_tuned("fused_conv_block", x, w, None, stride=(1, 1),
             policy=ExecPolicy(backend="pallas"))
assert len(TUNING_CACHE) >= 1
TUNING_CACHE.save(sys.argv[1])
print(f"wrote {len(TUNING_CACHE)} entries")
PY
PYTHONPATH=src python -m repro.launch.serve --arch mnist_cnn --capacity 4 \
  --requests 6 --tuning-cache "$TUNE_TMP/cache.json" --autotune \
  | tee "$TUNE_TMP/serve.log"
grep -q "tuning cache: loaded 1 entries" "$TUNE_TMP/serve.log"
grep -q "autotuned stages" "$TUNE_TMP/serve.log"

echo "== serve_slo smoke (front-end SLO bench, virtual clock, schema gate) =="
PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_slo \
  --smoke --virtual --out "$TUNE_TMP/slo.json"
PYTHONPATH=src:. python - "$TUNE_TMP/slo.json" <<'PY'
import json, sys
from benchmarks.serve_slo import check_schema
history = json.loads(open(sys.argv[1]).read())
assert isinstance(history, list) and history, "BENCH_slo.json not a history list"
check_schema(history[-1])
print(f"BENCH_slo schema OK ({len(history)} point(s))")
PY

echo "== plan-artifact smoke (cross-process save -> zero-derivation boot, bitwise parity) =="
PYTHONPATH=src python - "$TUNE_TMP/plans" <<'PY'
import sys
import jax
import numpy as np
from repro.models.cnn import PaperCNN, PaperCNNConfig
m = PaperCNN(PaperCNNConfig())
p = m.init(jax.random.PRNGKey(0))
b = m.compile(batch=2).bind(p)
x = jax.random.normal(jax.random.PRNGKey(1), (2, *m.input_shape()[1:]))
fp = b.save(sys.argv[1] + "/bucket_2", input_shapes=[tuple(x.shape)])
np.save(sys.argv[1] + "/want.npy", np.asarray(b(x)))
print(f"saved plan artifact fingerprint={fp[:16]}")
PY
PYTHONPATH=src python - "$TUNE_TMP/plans" <<'PY'
import sys
import jax
import jax.numpy as jnp
import numpy as np
from repro.artifact import load_plan
from repro.artifact.warmup import collect_warmup
from repro.models.cnn import PaperCNN, PaperCNNConfig
m = PaperCNN(PaperCNNConfig())
p = m.init(jax.random.PRNGKey(0))
with collect_warmup() as rep:
    art = load_plan(sys.argv[1] + "/bucket_2", params=p)
assert rep.zero_compile(), "artifact boot ran derivation:\n" + rep.pretty()
x = jax.random.normal(jax.random.PRNGKey(1), (2, *m.input_shape()[1:]))
got = np.asarray(art.program(tuple(x.shape))(jnp.asarray(x)))
np.testing.assert_array_equal(got, np.load(sys.argv[1] + "/want.npy"))
assert art.restored_aot(tuple(x.shape)), "AOT executable did not restore"
print("cross-process roundtrip: zero derivation, AOT restored, bitwise-equal")
PY

echo "== plan-artifact fallback gate (corrupt / unknown schema: warn, never crash) =="
PYTHONPATH=src python - "$TUNE_TMP/plans" <<'PY'
import json
import shutil
import sys
import warnings
from repro.artifact import PlanStore
root = sys.argv[1]
for case in ("corrupt", "badschema"):
    shutil.copytree(f"{root}/bucket_2", f"{root}/{case}")
mf = f"{root}/corrupt/manifest.json"
open(mf, "w").write("{not json")
mf = f"{root}/badschema/manifest.json"
doc = json.load(open(mf))
doc["schema_version"] = 999
json.dump(doc, open(mf, "w"))
store = PlanStore(root)
for case in ("corrupt", "badschema"):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert store.load(case) is None, f"{case}: load did not fall back"
    assert any("falling back" in str(x.message) for x in w), \
        f"{case}: no fallback warning"
print("corrupt + unknown-schema artifacts warn and fall back (no crash)")
PY

echo "== plan_boot smoke (cold-boot bench: modes bitwise-equal, schema gate) =="
PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.plan_boot \
  --smoke --no-json

echo "== shard_sweep smoke (auto 2-D placement, 4 forced devices, monotonicity gate) =="
# the gate asserts the auto placement does not fall off between mesh=2
# and mesh=4 (ratio test with slack — see benchmarks/shard_sweep.py)
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.shard_sweep \
  --smoke --no-json --gate-monotonic

echo "== tier-1 tests =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
