"""Device busy microseconds per simulated point-cycle of the loop ops
under the program's ``noc.vc_drain`` scope (nested in ``noc.fabric``:
one drained VC per physical link, the escape VC first): the union of
their intervals per iteration, mean over devices; nothing where no loop
op carries the scope (a fabric without VCs, or a program without it)."""


def read(run):
    tr = run["trace"]
    s = (tr or {}).get("scope_s_per_iteration", {}).get("noc.vc_drain")
    return None if s is None else 1e6 * s / tr["points"]
