"""The yardstick: generator, plain references, load loop, statistics, trace
reduction. Nothing here imports dgraph_tpu or jax (trace_reduce imports
jax.profiler's reader, in a process of its own)."""
