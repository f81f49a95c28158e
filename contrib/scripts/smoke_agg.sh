#!/usr/bin/env bash
# CI smoke: tier-1 verify + the ISSUE-17 device-aggregation gates on CPU.
#
# Step 1 runs the tier-1 verify line from ROADMAP.md (set SMOKE_SKIP_T1=1
# to skip when the full suite already ran in an earlier CI stage).
# Step 2 forces the 8-virtual-device CPU mesh and asserts:
#   * the groupby battery (count + sum/min/max/avg terminals, value-key /
#     multi-key / plain-child fallback shapes) is byte-identical mesh vs
#     classic,
#   * every terminal shape — traversal chain AND aggregation — is ONE
#     mesh dispatch (dgraph_mesh_dispatches_total delta == 1) with a
#     terminal op recorded (dgraph_agg_terminal_ops_total delta == 1),
#   * whole-graph analytics (`pagerank`, `cc`, `triangles`: the one-chip
#     PR / WCC / LCC programs, no mesh) agree with the NetworkX oracles:
#     PageRank to 1e-6, CC components and triangle counts EXACT, on the
#     device path and on the host references (a delta overlay),
#   * /metrics exposes the dgraph_agg_* / dgraph_analytics_* series and
#     parses clean.
# Runs entirely on the XLA host platform — no TPU required.

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

echo "== device-aggregation smoke (forced 8-device CPU) =="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python - <<'PY'
import json

import numpy as np
import jax

assert len(jax.devices()) >= 8, jax.devices()

import networkx as nx

from dgraph_tpu.api.server import Node
from dgraph_tpu.obs import prom

SCHEMA = ("name: string @index(exact) .\nrating: float @index(float) .\n"
          "p0: [uid] .\np1: [uid] .\np2: [uid] .\nfollows: [uid] .\n")
N = 300
quads = []
for i in range(1, N + 1):
    quads.append(f'<0x{i:x}> <name> "node{i % 60}" .')
    quads.append(f'<0x{i:x}> <rating> "{(i * 13) % 100 / 10}"'
                 f'^^<xs:float> .')
    for attr, mul, off in (("p0", 3, 1), ("p1", 5, 2), ("p2", 7, 3),
                           ("follows", 11, 5)):
        for k in range(3):
            t = (i * mul + off + k) % N + 1
            if t != i:
                quads.append(f"<0x{i:x}> <{attr}> <0x{t:x}> .")

# groupby battery: (name, query, is_terminal) — terminal shapes must run
# chain + aggregation as ONE fused dispatch with a terminal op recorded
BATTERY = [
    ("gb_count", '{ q(func: eq(name, "node3")) { p0 @groupby(p2) '
                 '{ count(uid) } } }', True),
    ("gb_deep", '{ q(func: eq(name, "node3")) { p0 { p1 @groupby(p2) '
                '{ count(uid) } } } }', True),
    ("gb_aggs", '{ var(func: has(name)) { r as rating } '
                '  q(func: eq(name, "node3")) { p0 { p1 @groupby(p2) '
                '{ count(uid) s: sum(val(r)) m: min(val(r)) '
                '  x: max(val(r)) a: avg(val(r)) } } } }', True),
    ("gb_value_key", '{ q(func: eq(name, "node3")) { p0 { p1 '
                     '@groupby(name) { count(uid) } } } }', False),
    ("gb_plain_child", '{ q(func: eq(name, "node3")) { p0 { p1 '
                       '@groupby(p2) { count(uid) name } } } }', False),
]

plain = Node()
mesh = Node(mesh_devices=8, mesh_min_edges=1)
for nd in (plain, mesh):
    nd.alter(schema_text=SCHEMA)
    nd.mutate(set_nquads="\n".join(quads), commit_now=True)
    nd.task_cache = nd.result_cache = None

mdisp = mesh.metrics.counter("dgraph_mesh_dispatches_total")
mterm = mesh.metrics.counter("dgraph_agg_terminal_ops_total")
for name, q, terminal in BATTERY:
    a, _ = plain.query(q)
    mesh.query(q)                        # warm the fused program
    d0, t0 = mdisp.value, mterm.value
    b, _ = mesh.query(q)
    assert json.dumps(a, sort_keys=True, default=str) == \
        json.dumps(b, sort_keys=True, default=str), f"{name}: mesh != classic"
    if terminal:
        assert mdisp.value - d0 == 1, f"{name}: not ONE dispatch"
        assert mterm.value - t0 == 1, f"{name}: no terminal op"
    print(f"  {name}: identical"
          + ("; ONE dispatch + terminal op" if terminal else ""))

# -- analytics vs NetworkX oracles (one chip's programs: no mesh) --------
def follows_graph():
    g = nx.DiGraph()
    sub, indptr, idx = \
        plain._read_view(None)[1].pred("follows").csr.host_arrays()
    for j, u in enumerate(sub):
        for t in idx[indptr[j]: indptr[j + 1]]:
            g.add_edge(int(u), int(t))
    return g


def check_analytics(g, device):
    """pagerank to 1e-6 and cc exact on the path `device` names; triangles
    exact (stored one way, `follows` always takes the host reference)."""
    pr = plain.analytics("pagerank", "follows", tol=1e-10, max_iters=300)
    oracle = nx.pagerank(g, alpha=0.85, tol=1e-13, max_iter=1000)
    want = {hex(u): s for u, s in oracle.items()}
    for row in pr["top"]:
        assert abs(row["score"] - want[row["uid"]]) < 1e-6, row
    cc = plain.analytics("cc", "follows")
    assert cc["components"] == nx.number_connected_components(
        g.to_undirected())
    assert pr["device"] is cc["device"] is device
    tr = plain.analytics("triangles", "follows")
    want_tri = sum(nx.triangles(g.to_undirected()).values()) // 3
    assert tr["triangles"] == want_tri and not tr["device"]
    return cc["components"], want_tri


comps, tri = check_analytics(follows_graph(), device=True)
# a commit makes the tablet a delta overlay: the host references answer
plain.mutate(set_nquads="<0x1> <follows> <0x3> .", commit_now=True)
check_analytics(follows_graph(), device=False)
print(f"  analytics: pagerank<=1e-6, cc={comps} exact, "
      f"triangles={tri} exact (device + host references)")

# -- /metrics exposes the new series and parses clean ---------------------
series = prom.parse(prom.render(mesh.metrics))
for want_series in ("dgraph_agg_terminal_ops_total",
                    "dgraph_analytics_runs_total",
                    "dgraph_analytics_edges_total"):
    assert any(k.startswith(want_series) for k in series), want_series
text = prom.render(mesh.metrics)
assert 'reason="groupby"' in text or 'reason="agg"' in text
n_series = sum(1 for k in series
               if k.startswith(("dgraph_agg", "dgraph_analytics")))
print(f"  /metrics: {n_series} dgraph_agg_*/dgraph_analytics_* series")
plain.close()
mesh.close()
print("OK: device-aggregation smoke passed")
PY
echo "== smoke passed =="
