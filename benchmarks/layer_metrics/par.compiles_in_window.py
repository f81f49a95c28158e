"""Programs compiled or loaded from the persistent cache between the
`khop-par22` window's first and last request (/debug/compiles: compiles +
cache hits). Should be 0 at every occupancy 22 clients can form: a program
that loads inside the window stalls every request stacked behind it."""


def read(run):
    return float(run.after["programs_loaded"]
                 - run.before["programs_loaded"])
