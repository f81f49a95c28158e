"""Of the sampled `khop<k>` requests, the share whose trace (/debug/traces,
span_sample 1.0 in the traced run) holds a device_kernel span of the family
the configuration names for the op (`pb.recurse_fused`). Under 100 the
executor served some of them from a host tier, or from a cache."""


def read(run):
    ev = [e for e in run.kernel_evidence if e["op"].startswith("khop")]
    if not ev:
        return None
    return 100.0 * sum(1 for e in ev if e["found"]) / len(ev)
