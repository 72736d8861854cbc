"""Device busy microseconds per simulated point-cycle of the loop ops
under none of the program's four phase scopes (layout copies, the rest
of the scan): the union of their intervals per iteration, mean over
devices; nothing where no loop op carries a phase."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.get("phase_s_per_iteration"):
        return None
    return 1e6 * tr["unscoped_s_per_iteration"] / tr["points"]
