"""Graph datasets & generators for tests and benchmarks.

The reference benchmarks against a 1.1M-edge film graph ("goldendata",
contrib/scripts/load-test.sh) and the north star targets LDBC-SNB-style
friends-of-friends traversal (BASELINE.json). This package provides:

  rmat:  R-MAT power-law graph generator (LDBC-ish degree skew) — the
         benchmark workload generator.
  film:  a small deterministic film graph (directors/actors/genres) used by
         engine tests and examples, in the spirit of the reference's
         query/benchmark movie-graph fixtures.
"""

from dgraph_tpu.models.rmat import rmat_edges, rmat_csr  # noqa: F401
