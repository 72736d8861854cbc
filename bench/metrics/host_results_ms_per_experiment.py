"""Host milliseconds per experiment, in the traced boundary between two
calls, in which the finishing call's ``noc.results`` span is the
innermost open (copies to the host, the ``SimResult``); mean over
devices; nothing where no program span covers the boundary."""


def read(run):
    tr = run["trace"]
    if tr is None or tr.get("host_results_s_per_experiment") is None:
        return None
    return 1e3 * tr["host_results_s_per_experiment"]
