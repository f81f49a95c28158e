#!/usr/bin/env bash
# CI smoke: tier-1 verify + a short CPU batched-dispatch check (ISSUE 9).
#
# Step 1 runs the tier-1 verify line from ROADMAP.md (set SMOKE_SKIP_T1=1 to
# skip when the full suite already ran in an earlier CI stage).
# Step 2 runs the cache-busting distinct-query battery (bench.py
# bench_batch) at reduced scale and asserts
#   * every batched TaskResult byte-identical to batching-off (--no_batch)
#     solo execution across the whole distinct-task pool,
#   * batch occupancy > 1 at concurrency 32 (batches actually formed),
#   * batching-on c=32 device-path QPS beats batching-off on the
#     emulated-device-sync sweep (a seeded delay, not a device number),
# then replays distinct queries against a batching Node vs a --no_batch
# Node end-to-end (flags surface) and checks the dgraph_batch_* series on
# /debug/metrics. Runs entirely on the XLA host platform — no TPU needed.

set -euo pipefail
cd "$(dirname "$0")/../.."

SMOKE_MIN_DOTS="${SMOKE_MIN_DOTS:-480}"
if [ "${SMOKE_SKIP_T1:-0}" != "1" ]; then
  echo "== tier-1 verify =="
  rm -f /tmp/_t1.log
  timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log || true
  dots=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
  echo "DOTS_PASSED=$dots (floor $SMOKE_MIN_DOTS)"
  if [ "$dots" -lt "$SMOKE_MIN_DOTS" ]; then
    echo "tier-1 regressed below the seed floor" >&2
    exit 1
  fi
fi

echo "== batched-dispatch smoke (CPU) =="
JAX_PLATFORMS=cpu python - <<'PY'
from bench import bench_batch

# reduced scale: does not clobber the full-scale BATCH_r09.json artifact
r = bench_batch(n_subjects=2000, pool=96, reps=2)
print(f"  occupancy {r.get('c32_occupancy_mean')} over "
      f"{r.get('c32_batches_formed')} batches; "
      f"on c32 {r['qps_on']['c32']['median']}/s vs "
      f"off c32 {r['qps_off']['c32']['median']}/s "
      f"({r['speedup_on_vs_off_c32']}x), "
      f"on c1 {r['qps_on']['c1']['median']}/s "
      f"({r['speedup_on_c32_vs_on_c1']}x)")
assert r["identical"], "batched outputs diverged from --no_batch solo"
assert r.get("c32_occupancy_mean", 0) > 1, \
    f"no batches formed at c=32: {r.get('c32_occupancy_mean')}"
assert r["speedup_on_vs_off_c32"] >= 1.2, \
    f"batching-on did not beat batching-off: {r['speedup_on_vs_off_c32']}x"

# -- flags end-to-end: batching Node vs --no_batch Node, byte-identical ---
import threading

import numpy as np

from dgraph_tpu.api.server import Node
from dgraph_tpu.query import task as taskmod

taskmod.HOST_EXPAND_MAX = 0          # device-class expands on a CPU graph


def build(**kw):
    node = Node(planner=False, task_cache_mb=0, result_cache_mb=0, **kw)
    node.alter(schema_text="follows: [uid] .")
    node.mutate(set_nquads="\n".join(
        f'<0x{i:x}> <follows> <0x{(i * 3) % 40 + 1:x}> .'
        for i in range(1, 41)), commit_now=True)
    return node


queries = [f'{{ q(func: uid(0x{i:x}, 0x{i + 1:x})) '
           f'{{ follows {{ uid }} }} }}' for i in range(1, 33, 2)]
plain = build(batching=False)
want = [plain.query(q)[0] for q in queries]
assert plain.batcher is None
plain.close()

node = build(batch_window_ms=50, batch_max=8)
assert node.batcher is not None
outs = [None] * len(queries)
barrier = threading.Barrier(len(queries))


def run(i):
    barrier.wait(timeout=30)
    outs[i] = node.query(queries[i])[0]


ts = [threading.Thread(target=run, args=(i,)) for i in range(len(queries))]
for t in ts:
    t.start()
for t in ts:
    t.join(60)
assert outs == want, "batching Node diverged from --no_batch Node"

from dgraph_tpu.api.http import _serving_metrics

m = _serving_metrics(node)["batching"]
assert m["enabled"] and m["formed"] >= 1 and m["batched_tasks"] >= 2, m
assert m["occupancy"]["max"] > 1, m
node.close()
print(f"  flags e2e: {len(queries)} distinct queries byte-identical, "
      f"{m['formed']} batches on /debug/metrics")
print("OK: byte-identity gate, occupancy gate, on-vs-off gate, flags e2e")
PY
echo "== smoke passed =="
