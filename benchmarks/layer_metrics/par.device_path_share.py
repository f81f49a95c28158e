"""Of the sampled `khop1` requests of the `khop-par22` cell, the share
whose trace (/debug/traces, span_sample 1.0 in the traced run) holds a
device_kernel span of a family the configuration names for the op:
`batch.recurse` (a stacked launch: the leader's own span, or the span of
a follower's wait for it) or `pb.recurse_fused` (it ran alone). Under 100
the executor served some of them from a host tier or a cache — or a
follower's trace does not say which launch answered it."""


def read(run):
    ev = [e for e in run.kernel_evidence if e["op"] == "khop1"]
    if not ev:
        return None
    return 100.0 * sum(1 for e in ev if e["found"]) / len(ev)
