"""Of the sampled requests of ops for which the configuration names a
device kernel, the share whose trace (/debug/traces, span_sample 1.0 in
the traced run) holds a device_kernel span of that family. Under 100 the
executor served some of them from a host tier."""

def read(run):
    ev = run.kernel_evidence
    if not ev:
        return None
    return 100.0 * sum(1 for e in ev if e["found"]) / len(ev)
