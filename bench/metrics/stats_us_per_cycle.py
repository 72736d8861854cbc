"""Device busy microseconds per simulated point-cycle of the loop ops
under the program's ``noc.stats`` phase scope (VC occupancy, per-class
statistics, liveness): the union of their intervals per iteration,
mean over devices; nothing where no loop op carries a phase."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.get("phase_s_per_iteration"):
        return None
    return 1e6 * tr["phase_s_per_iteration"]["noc.stats"] / tr["points"]
