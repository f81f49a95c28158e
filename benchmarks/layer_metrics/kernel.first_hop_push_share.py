"""Of the window's `pb.bfs_dist` searches, the share whose first level read
the root's own forward row ("push": out-degree at or under the program's
FIRST_HOP_CAP) and not the whole in-edge stream: 100 x growth of
`dgraph_bfs_first_hop_total{mode="push"}` / growth of both modes
(/metrics; the program shows both modes from its start, at 0). A program
without the counter has neither series: None, the metric is left out. A
window without such a search (a rehearsal on a CPU serves `shortest` from
the host tiers) reads 0, as exec.device_path_share does there."""

SERIES = 'dgraph_bfs_first_hop_total{mode="%s"}'


def read(run):
    if not any(SERIES % m in run.after["prom"] for m in ("push", "stream")):
        return None
    push, stream = (run.grown(SERIES % m) for m in ("push", "stream"))
    return 100.0 * push / (push + stream) if push + stream else 0.0
