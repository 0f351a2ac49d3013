"""Engine step: mean device time of one run of the jitted decode program
(``Engine._decode``) in the traced window, on the first chip."""

PROGRAM = r"^jit_decode\b"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    dev = run.trace.devices[0]
    evs = run.trace.module_events(PROGRAM, dev)
    return 1e3 * sum(e.dur_ns for e in evs) / len(evs) / 1e9 if evs else None
