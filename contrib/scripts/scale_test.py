"""10M-edge scale test (VERDICT r3 #8): bulk-load an R-MAT graph, measure
cold open, run a query battery under a --memory_mb budget.

Usage: python contrib/scripts/scale_test.py [scale] [edge_factor]
"""

import os
import sys
import tempfile
import time

# a host-path scale gate (ingest, cold open, paged store): pinned to CPU,
# set before anything imports jax. Its timings are host timings.
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.getcwd())

import numpy as np                                       # noqa: E402

from dgraph_tpu.models.rmat import rmat_csr              # noqa: E402


def main():
    scale = int(sys.argv[1]) if len(sys.argv) > 1 else 19
    ef = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    subjects, indptr, indices = rmat_csr(scale, ef, seed=42)
    E = len(indices)
    print(f"R-MAT scale {scale}, {E / 1e6:.1f}M edges, "
          f"{len(subjects) / 1e3:.0f}k subjects")

    tmp = tempfile.mkdtemp(prefix="dgraph-tpu-scale-")
    rdf = os.path.join(tmp, "graph.rdf")
    t0 = time.time()
    src = np.repeat(subjects, np.diff(indptr))
    with open(rdf, "w") as f:
        # uid edges + a value predicate on every subject
        for s, d in zip(src.tolist(), indices.tolist()):
            f.write(f"<0x{s + 1:x}> <follows> <0x{d + 1:x}> .\n")
        for s in subjects.tolist():
            f.write(f'<0x{s + 1:x}> <score> "{s % 1000}"^^<xs:int> .\n')
    print(f"RDF written in {time.time() - t0:.1f}s "
          f"({os.path.getsize(rdf) / 1e6:.0f} MB)")

    from dgraph_tpu.loader.bulk import bulk_load

    out = os.path.join(tmp, "p")
    t0 = time.time()
    stats = bulk_load([rdf], "follows: [uid] .\nscore: int @index(int) .",
                      out)
    dt = time.time() - t0
    nq = E + len(subjects)
    print(f"bulk load: {nq / 1e6:.1f}M quads in {dt:.1f}s "
          f"({nq / dt / 1e3:.0f}k quads/s)")

    from dgraph_tpu.api.server import Node

    t0 = time.time()
    node = Node(out)
    t_open = time.time() - t0
    t0 = time.time()
    hub = int(subjects[np.argmax(np.diff(indptr))]) + 1
    q = (f'{{ q(func: uid(0x{hub:x})) {{ c : count(follows) '
         f'follows (first: 3) {{ follows (first: 2) {{ uid }} }} }} }}')
    out1, _ = node.query(q)
    t_q1 = time.time() - t0
    assert out1["q"][0]["c"] > 0
    t0 = time.time()
    out2, _ = node.query('{ q(func: eq(score, 7)) { count(uid) } }')
    t_q2 = time.time() - t0
    assert out2["q"][0]["count"] > 0
    print(f"cold open {t_open:.1f}s; first 2-hop query {t_q1:.1f}s; "
          f"indexed eq {t_q2:.2f}s")

    # memory budget: force rollup + cache drop, verify queries still correct
    mem0 = node.store.memory_stats()["bytes"]
    budget = int(mem0 * 0.7)
    t0 = time.time()
    st = node.enforce_memory(budget)
    out3, _ = node.query('{ q(func: eq(score, 7)) { count(uid) } }')
    assert out3 == out2, "results diverged under memory pressure"
    print(f"memory budget {budget / 1e6:.0f}MB: {st}; "
          f"re-query OK in {time.time() - t0:.1f}s")
    node.close()

    # PAGED store (VERDICT r4 #4 done gate): reopen with a cap at HALF the
    # eager resident size — mmap'd segments + lazy lists + eviction — and
    # re-answer the battery with identical results
    from dgraph_tpu.api.server import Node as _Node

    cap = mem0 // 2
    t0 = time.time()
    pnode = _Node(out, memory_mb=max(1, cap // (1 << 20)))
    pnode.store.memory_budget = cap
    t_popen = time.time() - t0
    t0 = time.time()
    pq1, _ = pnode.query(q)
    t_pq1 = time.time() - t0
    assert pq1 == out1, "paged 2-hop diverged"
    pq2, _ = pnode.query('{ q(func: eq(score, 7)) { count(uid) } }')
    assert pq2 == out2, "paged indexed eq diverged"
    pnode.store._evict_clean()
    pst = pnode.store.memory_stats()
    assert pst["bytes"] <= cap, (pst, cap)
    print(f"paged @ {cap / 1e6:.0f}MB cap (half of eager {mem0 / 1e6:.0f}MB):"
          f" open {t_popen:.1f}s, first 2-hop {t_pq1:.1f}s, resident "
          f"{pst['bytes'] / 1e6:.0f}MB over {pst['lists']} lists "
          f"({pst['segment_keys']} segment keys)")
    pnode.close()
    print("SCALE TEST PASSED")


if __name__ == "__main__":
    main()
