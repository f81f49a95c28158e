"""Programs compiled or loaded from the persistent cache between the
`graphalytics` window's first and last request (/debug/compiles: compiles
+ cache hits). Should be 0: one program a kind, the probes padded to one
class, each warmed in set-up."""


def read(run):
    return float(run.after["programs_loaded"]
                 - run.before["programs_loaded"])
