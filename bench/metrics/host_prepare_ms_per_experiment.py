"""Host milliseconds per experiment, in the traced boundary between two
calls, in which the next call's ``noc.verify``, ``noc.schedules``,
``noc.build`` or ``noc.dispatch`` span is the innermost open (its
schedules, checks, copies to the device and dispatch); mean over
devices; nothing where no program span covers the boundary."""


def read(run):
    tr = run["trace"]
    if tr is None or tr.get("host_prepare_s_per_experiment") is None:
        return None
    return 1e3 * tr["host_prepare_s_per_experiment"]
