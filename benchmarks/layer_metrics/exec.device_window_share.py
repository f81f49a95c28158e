"""Share of the requests' wall time spent inside the executor's device
windows: the growth of `dgraph_query_cost_device_ms_sum` (/metrics) over
the window, over the summed client latency of its requests. A device
window is a host-clock span around a call that ends in a fetch; sound for
the families PR 21 fenced (pb.bfs_dist, pb.recurse_fused, pb.recurse_step,
traversal.sssp, segments.lens_reduce, dist.expand), an enqueue only for
batch.recurse. The run's stderr names the families that hold programs."""

def read(run):
    wall_ms = sum((r["t_done"] - r["t_send"]) * 1e3 for r in run.reqs)
    if "dgraph_query_cost_device_ms_sum" not in run.after["prom"] \
            or not wall_ms:
        return None
    return 100.0 * run.grown("dgraph_query_cost_device_ms_sum") / wall_ms
