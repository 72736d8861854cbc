"""The device loop split by the program's scopes, and the host gap
between two calls split by the program's own spans.

The program runs every operation of its scan step under one of four
outermost ``jax.named_scope``s (:data:`PHASES`), possibly with further
``noc.*`` scopes nested inside, and marks each API call's host work
with ``jax.profiler.TraceAnnotation`` spans that carry ``call=<id>``
(``noc.verify`` ... ``noc.results``, see ``repro.noc.span_stats``).
Both land in the same profiler trace as the device ops, so this module
splits what ``bench/tracing.py`` measures:

* busy time per iteration of the device loop, by the outermost phase
  of each op's op-name metadata (a fusion carries its root's), with
  the ops that carry no phase as the unscoped rest; and by every
  ``noc.*`` scope, a nested one counting in its own entry and in each
  enclosing scope's, so a scope the program adds is a key, not code;
* the boundary between two device programs, by the innermost program
  span open at each instant: the finishing call's ``noc.results``, the
  next call's ``noc.verify``/``noc.schedules``/``noc.build``/
  ``noc.dispatch``, and what no program span covers; and by the time
  each span name covers.

Planes are those of ``bench/tracing.py`` with an optional fourth
element per device event, its scope path (``noc.fabric/noc.vc_arb``,
outermost first; ``None`` for none), and host events ``(name,
start_ns, end_ns, stats)``; 3-tuples reduce as unscoped ops and spans
without a call id.  The benchmark's traced run reads a trace with
:func:`load` and merges both splits into its reduction
(``bench/tracing.py``).

``python bench/phases.py --workload <cell> --seed <n>`` runs a cell's
warm call, a few timed calls and one traced boundary on the chip, and
prints both splits beside the existing reduction's numbers; ``--out
<dir>`` keeps the whole output as ``<dir>/<cell>.json``.
"""
from __future__ import annotations

import collections
import functools
import re
import sys
from pathlib import Path
from typing import Callable

if __package__ in (None, ""):                  # run as a script
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import tracing  # noqa: E402

# the program's scope and span names, spelled out: the reduction reads
# a trace, not the program, so one without them reduces to nothing
PHASES = ("noc.ni_issue", "noc.fabric", "noc.ni_rings", "noc.stats")
PREPARE = ("noc.verify", "noc.schedules", "noc.build", "noc.dispatch")
RESULTS = ("noc.results",)
SPAN_PREFIX = "noc."              # of the program's spans and scopes
# event stats that may hold an op's op-name metadata on a device plane
OP_NAME_STATS = ("tf_op", "op_name", "long_name", "name")
NO_SPAN = "no program span"


@functools.lru_cache(maxsize=4096)
def phase_of(op_name: str | None) -> str | None:
    """The outermost phase scope in an op name such as
    ``jit(run)/while/body/closed_call/noc.fabric/fabric_front/gather``
    (``noc.fabric``) or in a scope path, or ``None`` where it holds
    none."""
    for part in (op_name or "").split("/"):
        if part in PHASES:
            return part
    return None


@functools.lru_cache(maxsize=4096)
def scope_names(op_name: str | None) -> tuple[str, ...]:
    """Every ``noc.*`` scope in an op name or scope path, outermost
    first, each once."""
    return tuple(dict.fromkeys(p for p in (op_name or "").split("/")
                               if p.startswith(SPAN_PREFIX)))


def scopes_of(op_name: str | None) -> str | None:
    """An op name's scope path:
    ``jit(run)/while/body/noc.fabric/fabric_update/noc.vc_arb/select``
    -> ``noc.fabric/noc.vc_arb``; ``None`` where it holds none."""
    return "/".join(scope_names(op_name)) or None


def scopes_of_stats(stats: dict) -> str | None:
    """The scope path of an event's op-name stats, if any."""
    for k in OP_NAME_STATS:
        v = stats.get(k)
        if isinstance(v, str) and scopes_of(v) is not None:
            return scopes_of(v)
    return None


_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.-]+)")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction: scope path}`` from a compiled module's HLO text:
    each instruction's own op-name metadata or, for a fusion (any call)
    without one, its called computation's root's — and where the
    compiler left the root without metadata (a rewritten scatter), the
    scopes of the computation's last instruction that has any."""
    own, calls, roots, last, comp = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            comp = c.group(1) if c else comp
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = scopes_of(op.group(1)) if op else None
        if own[name] is not None and comp is not None:
            last[comp] = own[name]
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1) and comp is not None:
            roots[comp] = name
    out = {}
    for name in own:
        n, p, seen = name, own[name], set()
        while p is None and n in calls and calls[n] not in seen:
            comp = calls[n]
            seen.add(comp)
            n = roots.get(comp, "")
            p = own.get(n) or (None if n in calls else last.get(comp))
        if p is not None:
            out[name] = p
    return out


def op_key(event_name: str) -> str:
    """An event's instruction name: ``%fusion.3 = s32[...] ...`` ->
    ``fusion.3``."""
    return tracing.short_name(event_name).split(" ")[0].lstrip("%")


def _scopes(ev) -> str | None:
    return ev[3] if len(ev) > 3 else None


def reduce_phases(planes, points: int, top: int = 10) -> dict | None:
    """Busy seconds per loop iteration by phase and by scope, mean over
    devices.

    Loop ops, iterations and the boundary are found as
    :func:`bench.tracing.reduce` finds them; each phase is the union of
    its loop ops' intervals on the ``XLA Ops`` line, the rest that of
    the loop ops with no phase, so on a line whose ops do not overlap
    the phases and the rest add up to ``busy_s_per_iteration``.
    ``scope_s_per_iteration`` has one entry for every ``noc.*`` scope
    found: the union of the loop ops under it, nested scopes included
    (a ``noc.fabric/noc.vc_arb`` op counts in both entries).  ``None``
    where the trace holds no device loop."""
    devs = [ev for _, ev, _ in tracing.device_lines(planes) if ev]
    if not devs:
        return None
    per, rest_ops, ops = [], collections.Counter(), collections.Counter()
    for events in devs:
        names = collections.Counter(e[0] for e in events)
        iters = tracing.loop_iterations(names)
        if iters is None:
            return None
        loop = [e for e in events if names[e[0]] >= 3]
        busy, _ = tracing.union_length([(e[1], e[2]) for e in loop])
        by = {p: [] for p in PHASES + (None,)}
        scopes = collections.defaultdict(list)
        for e in loop:
            phase = phase_of(_scopes(e))
            by[phase].append((e[1], e[2]))
            for sc in scope_names(_scopes(e)):
                scopes[sc].append((e[1], e[2]))
            ops[f"{phase or 'unscoped'} {tracing.short_name(e[0])}"] += \
                (e[2] - e[1]) / iters / len(devs)
            if phase is None:
                rest_ops[tracing.short_name(e[0])] += \
                    (e[2] - e[1]) / iters / len(devs)
        per.append({"busy": busy / iters,
                    **{p: tracing.union_length(v)[0] / iters
                       for p, v in by.items()},
                    "scopes": {sc: tracing.union_length(v)[0] / iters
                               for sc, v in scopes.items()}})
    n = len(per)

    def mean(k):
        return sum(p[k] for p in per) / n / 1e9

    scoped = any(p[k] for p in per for k in PHASES)
    found = dict.fromkeys(sc for p in per for sc in p["scopes"])
    return {
        "devices": n,
        "points": points,
        "busy_s_per_iteration": mean("busy"),
        # None where no loop op carries a phase (a program without the
        # scopes): nothing to split
        "phase_s_per_iteration": ({p: mean(p) for p in PHASES}
                                  if scoped else None),
        "unscoped_s_per_iteration": mean(None),
        "scope_s_per_iteration": {
            sc: sum(p["scopes"].get(sc, 0) for p in per) / n / 1e9
            for sc in found},
        "unscoped_ops": [[k, v / 1e9] for k, v in rest_ops.most_common(top)],
        "device_ops": [[k, v / 1e9] for k, v in ops.most_common(top)],
        # every loop op: its phase and seconds per iteration
        "loop_ops": [[k.split(" ", 1)[1], k.split(" ", 1)[0], v / 1e9]
                     for k, v in ops.most_common()],
    }


def host_spans(planes, since: int | None = None) -> list[tuple]:
    """The program's spans on the host planes, ``(name, start, end,
    call)``, leaving out any that began before ``since`` (the first
    device op of the window: a span that began before the trace is cut
    or missing, never whole)."""
    out = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                name, s, e = ev[:3]
                if not name.startswith(SPAN_PREFIX):
                    continue
                if since is not None and s < since:
                    continue
                stats = ev[3] if len(ev) > 3 else {}
                out.append((name, s, e, stats.get("call")))
    return out


def innermost(spans, t0: int, t1: int) -> list[tuple]:
    """``[(span or None, start, end)]``: ``[t0, t1)`` cut where the
    innermost span open changes (the latest begun of those open)."""
    cuts = sorted({t0, t1, *(x for sp in spans for x in sp[1:3]
                             if t0 < x < t1)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [sp for sp in spans if sp[1] <= a and sp[2] >= b]
        inner = max(open_, key=lambda sp: (sp[1], -sp[2]), default=None)
        if out and out[-1][0] is inner:
            out[-1] = (inner, out[-1][1], b)
        else:
            out.append((inner, a, b))
    return out


def boundary_split(bound, spans) -> dict:
    """The boundary ``(b0, b1)`` between two device programs by the
    innermost program span: parts named ``host finishing a call
    (<span>)`` for the earlier call's spans, ``host starting a call
    (<span>)`` for the next call's (told apart by ``call`` id; with one
    id seen, by the span: results and waits finish a call, the rest
    start one), and the time no span covers; the next call's prepare
    spans and the finishing call's results as totals.  The finishing
    call's ``noc.wait`` began before the trace, so it is inferred: the
    uncovered stretch before that call's ``noc.results``."""
    b0, b1 = bound
    spans = [sp for sp in spans if sp[1] < b1 and sp[2] > b0]
    ids = sorted({sp[3] for sp in spans if sp[3] is not None})

    def finishing(sp):
        if len(ids) > 1:
            return sp[3] != ids[-1]
        return sp[0] in RESULTS + ("noc.wait",)

    # the finishing call's noc.wait began before the trace and is not
    # in it, but its noc.results starts as the wait ends: the stretch
    # before that first recorded span is the wait's
    first = min((sp for sp in spans if finishing(sp)),
                key=lambda sp: sp[1], default=None)
    waited = first[1] if first and first[0] in RESULTS else b0
    parts, prepare, results = collections.Counter(), 0, 0
    for sp, a, b in innermost(spans, b0, b1):
        if sp is None:
            name = ("host finishing a call (noc.wait)" if b <= waited
                    else f"host between calls ({NO_SPAN})")
            parts[name] += b - a
            continue
        role = "finishing" if finishing(sp) else "starting"
        parts[f"host {role} a call ({sp[0]})"] += b - a
        if role == "starting" and sp[0] in PREPARE:
            prepare += b - a
        elif role == "finishing" and sp[0] in RESULTS:
            results += b - a
    by_span = collections.defaultdict(list)
    for name, s, e, _ in spans:
        by_span[name].append((max(s, b0), min(e, b1)))
    return {"parts": dict(parts), "prepare": prepare, "results": results,
            "spans": len(spans),
            "by_span": {k: tracing.union_length(v)[0]
                        for k, v in by_span.items()}}


def reduce_host(planes) -> dict | None:
    """The boundary of each device's loop (as
    :func:`bench.tracing.reduce` finds it) split by program spans, and
    the time of it each span name covers, mean over devices; ``None``
    where the window holds no boundary or no program span covers any
    of it."""
    devs = [ev for _, ev, _ in tracing.device_lines(planes) if ev]
    splits = []
    for events in devs:
        names = collections.Counter(e[0] for e in events)
        loop = [(e[1], e[2]) for e in events if names[e[0]] >= 3]
        if not loop:
            return None
        _, gaps = tracing.union_length(loop)
        bound = max(gaps, key=lambda g: g[1] - g[0], default=None)
        if bound is None or bound[1] - bound[0] <= tracing.BOUNDARY_NS:
            return None
        since = min(s for s, _ in loop)
        split = boundary_split(bound, host_spans(planes, since))
        split["boundary"] = bound[1] - bound[0]
        # ops outside the loop in the boundary: the end of one device
        # program (post-loop reductions) and the start of the next
        split["device"], _ = tracing.union_length(
            [(max(e[1], bound[0]), min(e[2], bound[1])) for e in events
             if e[1] < bound[1] and e[2] > bound[0]])
        splits.append(split)
    if not splits or not all(s["spans"] for s in splits):
        return None
    n = len(splits)
    parts, by_span = collections.Counter(), collections.Counter()
    for s in splits:
        for k, v in s["parts"].items():
            parts[k] += v / n / 1e9
        for k, v in s["by_span"].items():
            by_span[k] += v / n / 1e9
    return {
        "host_s_per_experiment": sum(s["boundary"] for s in splits) / n / 1e9,
        "host_prepare_s_per_experiment":
            sum(s["prepare"] for s in splits) / n / 1e9,
        "host_results_s_per_experiment":
            sum(s["results"] for s in splits) / n / 1e9,
        "device_busy_s_in_boundary": sum(s["device"] for s in splits) / n
        / 1e9,
        "host_parts": [[k, v] for k, v in parts.most_common()],
        # the boundary's time under each span name, a nested span's in
        # its own entry and in each enclosing span's
        "span_s_per_experiment": dict(by_span),
    }


# --------------------------------------------------------------------- #
# reading a trace file, and the traced run on the chip
# --------------------------------------------------------------------- #
def load(path: Path, hlo: Callable[[], dict[str, str]] | None = None,
         sample: int = 0) -> tuple[list, list]:
    """The planes of an ``.xplane.pb`` with each device event's scope
    path (from its op-name stats where the trace has them — the TPU's
    has not — else from the instruction map ``hlo()`` returns, called
    once at the first op that needs it, so a trace without device ops
    lowers nothing) and each host event's stats; and the stats of up to
    ``sample`` distinct device ops, to read by hand."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    planes, seen, scope, table = [], {}, {}, None   # scope: per op, once
    for p in pd.planes:
        device = p.name.startswith("/device:")
        lines = []
        for ln in p.lines:
            events = []
            for e in ln.events:
                end = e.start_ns + e.duration_ns
                if device:
                    if e.name not in scope:
                        stats = {k: v for k, v in e.stats}
                        scope[e.name] = scopes_of_stats(stats)
                        if scope[e.name] is None and hlo is not None:
                            table = hlo() if table is None else table
                            scope[e.name] = table.get(op_key(e.name))
                        if len(seen) < sample:
                            seen[e.name] = {k: str(v)[:300]
                                            for k, v in stats.items()}
                    events.append((e.name, e.start_ns, end, scope[e.name]))
                elif e.name.startswith(SPAN_PREFIX):
                    events.append((e.name, e.start_ns, end,
                                   {k: v for k, v in e.stats}))
                else:
                    events.append((e.name, e.start_ns, end, {}))
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": p.name, "lines": lines})
    return planes, [[k[:200], v] for k, v in seen.items()]


def plain(planes) -> list:
    """``planes`` with every event cut to ``(name, start, end)``, the
    form :func:`bench.tracing.reduce` reads."""
    return [{"name": p["name"],
             "lines": [{"name": ln["name"],
                        "events": [tuple(e[:3]) for e in ln["events"]]}
                       for ln in p["lines"]]}
            for p in planes]


def compiled_hlo() -> dict[str, str]:
    """``{instruction: scope path}`` over the programs the process
    compiled (a cell runs one), each lowered once, for a trace whose
    device events carry no op names."""
    import jax
    from repro.noc import engine
    out = {}
    for (_, _, avals), prog in list(engine._programs.items()):
        shapes = [jax.ShapeDtypeStruct(s, d) for s, d in avals]
        out.update(hlo_scopes(prog.lower(*shapes).compile().as_text()))
    return out


def span_cost(n: int = 20000) -> float:
    """Host microseconds of one program span (annotation, two clock
    reads, the table's add) with the profiler off, over an empty
    loop's."""
    import time
    from repro.noc.engine import span
    t = time.perf_counter()
    for _ in range(n):
        pass
    bare = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(n):
        with span("bench.span_cost"):
            pass
    return 1e6 * (time.perf_counter() - t - bare) / n


def measure(cell, seed: int, calls: int, trace_root: Path) -> dict:
    """Warm call, ``calls`` timed calls, one traced boundary (kept, not
    deleted, until reduced): both splits, the setup spans, the timed
    calls' spans and the traced calls' times."""
    import shutil
    import time
    from bench import harness
    from repro.noc import span_stats, span_stats_clear

    t0 = time.perf_counter()
    system = harness.System(cell, seed)
    system.call(None)
    res = {"cell": cell.name, "setup_s": time.perf_counter() - t0,
           "setup_spans": span_stats()}
    span_stats_clear()
    secs = []
    for i in range(calls):
        a = time.perf_counter()
        system.call(i)
        secs.append(time.perf_counter() - a)
    res["untraced_call_s"] = secs
    res["window_spans_per_call"] = {k: v["seconds"] / calls
                                    for k, v in span_stats().items()}
    res["spans_per_call"] = sum(v["count"] for v in span_stats().values()
                                ) / calls
    res["us_per_span"] = span_cost()

    trace_dir = trace_root / f"phases_{cell.name}"
    res["traced_call_s"] = tracing.capture(system, trace_dir,
                                           sum(secs) / len(secs))
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    planes, res["op_stats_sample"] = load(files[0], hlo=compiled_hlo,
                                          sample=12)
    res["tracing"] = tracing.reduce(plain(planes), len(system.specs))
    res["phases"] = reduce_phases(planes, len(system.specs))
    res["host"] = reduce_host(planes)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return res


def summary(res: dict) -> dict:
    """Per point-cycle microseconds and per-experiment milliseconds of
    ``measure``'s result, under the names the splits would have as
    metrics."""
    out = {"cell": res["cell"],
           "setup_build_s": res["setup_spans"].get(
               "noc.build", {}).get("seconds")}
    ph, tr = res.get("phases"), res.get("tracing")
    if ph and tr and ph["phase_s_per_iteration"]:
        us = 1e6 / ph["points"]
        out["device_us_per_cycle"] = us * tr["busy_s_per_iteration"]
        for p in PHASES:
            out[p.split(".")[1] + "_us_per_cycle"] = \
                us * ph["phase_s_per_iteration"][p]
        out["unscoped_us_per_cycle"] = us * ph["unscoped_s_per_iteration"]
    host = res.get("host")
    if host:
        for k in ("host", "host_prepare", "host_results"):
            out[f"{k}_ms_per_experiment"] = \
                1e3 * host[f"{k}_s_per_experiment"]
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import os
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src")]
    from bench import harness
    cell = harness.resolve(args.workload, root)
    cache = root / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"phases: {args.workload} needs {cell.chips} TPU chip(s); "
              f"jax sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    res = measure(cell, args.seed, args.calls, root / ".bench_trace")
    res["device_kind"] = devices[0].device_kind
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
        (args.out / f"{cell.name}.json").write_text(json.dumps(res))
    print(json.dumps(summary(res)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
