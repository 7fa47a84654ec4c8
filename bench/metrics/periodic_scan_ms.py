"""Mean device time of one run of the periodic fleet scan program
(``jit__periodic_scan``)."""


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.modules("jit__periodic_scan")
    return 1000.0 * sum(e - s for s, e in runs) / len(runs) if runs else None
