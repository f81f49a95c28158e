"""Programs compiled or loaded from the persistent cache between the
`graphalytics-lcc` window's first and last request (/debug/compiles:
compiles + cache hits). Should be 0: one program, the probes padded to
one class, warmed in set-up."""


def read(run):
    return float(run.after["programs_loaded"]
                 - run.before["programs_loaded"])
