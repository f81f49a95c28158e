"""Of the window's fused `@recurse` traversals (`pb.recurse_fused` alone, or
a member of a stacked `pb.recurse_fused_multi` launch: each counts itself),
the share whose first level read the seeds' own forward rows ("push":
out-degree sum at or under the program's FIRST_HOP_CAP) and not the whole
in-edge stream: 100 x growth of
`dgraph_recurse_first_hop_total{mode="push"}` / growth of both modes
(/metrics; the program shows both modes from its start, at 0) —
kernel.first_hop_push_share's arithmetic over the recurse programs'
counter. A program without the counter has neither series: None, the
metric is left out. A window without such a traversal (a rehearsal on a CPU
serves `@recurse` from the host mirror) reads 0, as khop.device_path_share
does there."""

SERIES = 'dgraph_recurse_first_hop_total{mode="%s"}'


def read(run):
    if not any(SERIES % m in run.after["prom"] for m in ("push", "stream")):
        return None
    push, stream = (run.grown(SERIES % m) for m in ("push", "stream"))
    return 100.0 * push / (push + stream) if push + stream else 0.0
