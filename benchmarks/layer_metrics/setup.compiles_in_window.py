"""Programs compiled or loaded from the persistent cache between the
window's first and last request (/debug/compiles: compiles + cache hits).
Should be 0: warm-up is set-up's work."""

def read(run):
    return float(run.after["programs_loaded"]
                 - run.before["programs_loaded"])
