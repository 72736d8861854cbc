"""The traced sub-window and its reduction to device metrics.

A whole experiment runs the simulation loop for thousands of cycles at
hundreds of device operations per cycle, so its trace would hold
millions of events.  The traced run therefore records a bounded
sub-window across the boundary between two experiment calls made back
to back: the profiler starts ``LEAD_SECONDS`` before the first call is
expected to end (from the window's mean call time) and stops
``TRACE_SECONDS`` later, in the second call's loop.  The trace then
holds the end of one device loop, the host's work between the two
device programs (the first call's results, the second call's
schedules, checks and dispatch) and the start of the next loop.

Every operation of the loop body runs once per simulated cycle, so the
number of cycles the window saw is the most common event count among
the device's operation names, and those names are the loop's ops (ops
outside the loop run once per call).  The longest stretch with no loop
op is the boundary: the time per experiment that the device loop does
not run.  :func:`reduce` recovers all of it from the trace alone;
:func:`reduce_split` adds the split by the program's scopes and spans.
"""
from __future__ import annotations

import collections
import re
import shutil
import threading
import time
from pathlib import Path

COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")
SPAN = "bench.experiment"
START = "bench.call_start"        # instant marker: a call has begun
END = "bench.call_end"            # instant marker: a call has returned
# the trace starts this long before the first call's expected end ...
LEAD_SECONDS = 0.2
# ... and lasts this long: ~0.15 s of loop on each side of a host gap of
# 13-100 ms (~2500 7x7 cycles, ~600k events, ~50 MB of trace)
TRACE_SECONDS = 0.5
# a stretch without loop ops longer than this is the boundary between
# two device programs (inside the loop, ops are ~2 us apart)
BOUNDARY_NS = 1_000_000


def union_length(intervals: list[tuple[int, int]]) -> tuple[int, list]:
    """Total length of the union of ``(start, end)`` intervals and the
    gaps between the merged intervals, as ``(start, end)``."""
    total, gaps, cur = 0, [], None
    for s, e in sorted(intervals):
        if cur is None:
            cur = [s, e]
        elif s > cur[1]:
            total += cur[1] - cur[0]
            gaps.append((cur[1], s))
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    if cur is not None:
        total += cur[1] - cur[0]
    return total, gaps


def loop_iterations(names: collections.Counter) -> int | None:
    """The most common count among operation names seen more than once
    (ties to the larger): the loop body's iterations in the window."""
    counts = collections.Counter(c for c in names.values() if c > 1)
    if not counts:
        return None
    top = max(counts.values())
    return max(c for c, k in counts.items() if k == top)


def device_lines(planes) -> list:
    """``[(device plane name, ops, async ops)]`` for every device: the
    events of its ``XLA Ops`` line and of its ``Async XLA Ops`` line (the
    start halves of asynchronous copies and collectives), each as
    ``[(name, start_ns, end_ns), ...]``."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if "XLA Ops" in lines:
            out.append((plane["name"], lines["XLA Ops"],
                        lines.get("Async XLA Ops", [])))
    return out


def is_collective(op: str) -> bool:
    """Whether the op itself (its name or its kind, not an operand) is a
    collective: ``%collective-permute-done.2 = ...``, ``%x = ...
    all-reduce(...)``."""
    words = short_name(op).split(" ")
    return any(w.lstrip("%").startswith(k) for w in (words[0], words[-1])
               for k in COLLECTIVES)


def loop_collectives(events, names=None) -> list[tuple[int, int]]:
    """``(start, end)`` of the collective ops among ``events`` that run
    in the loop (their name at least three times), so that one outside
    it, as a result gather between calls, is left out.  The start half
    of an asynchronous collective and its done half can overlap: the
    caller takes the union, so no time counts twice."""
    names = names or collections.Counter(n for n, _, _ in events)
    return [(s, e) for n, s, e in events
            if names[n] >= 3 and is_collective(n)]


def host_markers(planes) -> tuple[list, list]:
    """Times of the bench's call-start and call-end markers on the host.
    (A span that began before the trace started is not recorded, so the
    boundary is told from markers, not spans.)"""
    starts, ends = [], []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for n, s, _ in line["events"]:
                if n == START:
                    starts.append(s)
                elif n == END:
                    ends.append(s)
    return starts, ends


def short_name(op: str) -> str:
    """``%fusion.3 = s32[49,5]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.3 s32[49,5] fusion``: the op, its result shape, its kind."""
    name, eq, rest = op.partition(" = ")
    if not eq:
        return op[:80]
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    kind = re.search(r"[})\]] ([\w-]+)\(", rest)
    return " ".join([name] + [m.group(m.re.groups) for m in (shape, kind)
                              if m])


def reduce(planes, points: int, top: int = 10) -> dict | None:
    """Device metrics of a traced sub-window.

    ``planes`` is ``[{"name", "lines": [{"name", "events": [(name,
    start_ns, end_ns)]}]}]``.  Busy time, span, op counts and idle gaps
    are taken over the loop's ops with the boundary between programs
    left out, collective time as the union of the loop's collective
    ops on both lines; ``host_s_per_experiment`` is the boundary itself
    (``None`` where the window holds none).  Returns ``None`` where the trace
    holds no device loop; per-device numbers are averaged over devices."""
    devs = [(ev, asy) for _, ev, asy in device_lines(planes) if ev]
    if not devs:
        return None
    markers = host_markers(planes)
    per, op_time, gaps_all = [], collections.Counter(), []
    for events, async_events in devs:
        names = collections.Counter(n for n, _, _ in events)
        iters = loop_iterations(names)
        if iters is None:
            return None
        loop = [(s, e) for n, s, e in events if names[n] >= 3]
        busy, gaps = union_length(loop)
        span = max(e for _, e in loop) - min(s for s, _ in loop)
        bound = max(gaps, key=lambda g: g[1] - g[0], default=None)
        if bound is not None and bound[1] - bound[0] > BOUNDARY_NS:
            gaps.remove(bound)
            span -= bound[1] - bound[0]
            gaps_all += boundary_parts(bound, *markers)
        else:
            bound = None
        coll, _ = union_length(loop_collectives(events, names)
                               + loop_collectives(async_events))
        for n, s, e in events:
            op_time[short_name(n)] += (e - s) / len(devs)
        per.append({"busy": busy, "span": span, "iters": iters,
                    "ops": sum(names[n] for n in names if names[n] >= 3),
                    "coll": coll,
                    "host": None if bound is None else bound[1] - bound[0]})
        gaps_all += [("between ops inside the device loop", e - s)
                     for s, e in gaps]
    n = len(per)

    def mean(k):
        return sum(p[k] for p in per) / n

    it = mean("iters")
    hosts = [p["host"] for p in per]
    gaps_all.sort(key=lambda g: -g[1])
    return {
        "devices": n,
        "iterations": it,
        "points": points,
        "busy_s": mean("busy") / 1e9,
        "window_s": mean("span") / 1e9,
        "busy_s_per_iteration": mean("busy") / 1e9 / it,
        "ops_per_iteration": mean("ops") / it,
        "collective_s_per_iteration": mean("coll") / 1e9 / it,
        "host_s_per_experiment": (None if None in hosts
                                  else sum(hosts) / n / 1e9),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in op_time.most_common(top)],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps_all[:top]],
        },
    }


def boundary_parts(bound, starts, ends) -> list[tuple[str, float]]:
    """The boundary between two device programs, split by what the host
    was doing: finishing the first call (until its end marker), between
    the calls, and starting the second (after its start marker)."""
    b0, b1 = bound
    end = [t for t in ends if b0 <= t <= b1]
    start = [t for t in starts if b0 <= t <= b1]
    if not end or not start or min(start) < max(end):
        return [("host between two device programs", b1 - b0)]
    end, start = max(end), min(start)
    return [(f"host finishing a call ({SPAN} open: results to host)",
             end - b0),
            ("host between calls (benchmark loop)", start - end),
            (f"host starting a call ({SPAN} open: schedules, checks, "
             f"dispatch)", b1 - start)]


def reduce_split(planes, points: int) -> dict | None:
    """:func:`reduce`'s numbers with the program's own split merged in
    (``bench/phases.py``): busy time by phase and by ``noc.*`` scope
    (``phase_s_per_iteration``, ``scope_s_per_iteration``,
    ``unscoped_s_per_iteration``) and the boundary by span
    (``host_prepare_s_per_experiment``,
    ``host_results_s_per_experiment``, ``span_s_per_experiment``).
    Where both give a key, :func:`reduce`'s value stays.  ``planes`` as
    ``bench.phases.load`` gives them; ``None`` where :func:`reduce`
    finds no device loop."""
    from bench import phases
    out = reduce(phases.plain(planes), points)
    if out is None:
        return None
    split = {**(phases.reduce_host(planes) or {}),
             **(phases.reduce_phases(planes, points) or {})}
    return {**split, **out}


def capture(system, trace_dir: Path, expected_s: float) -> list[float]:
    """Run two more experiment calls (the first expected to take
    ``expected_s``) with the profiler on across the boundary between
    them, its files under ``trace_dir``; returns the calls' seconds."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    secs = []

    def work():
        for experiment in (10 ** 6, 10 ** 6 + 1):
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation(START):
                pass
            with jax.profiler.TraceAnnotation(SPAN):
                system.call(experiment)
            with jax.profiler.TraceAnnotation(END):
                pass
            secs.append(time.perf_counter() - a)

    th = threading.Thread(target=work)
    th.start()
    time.sleep(max(0.0, expected_s - LEAD_SECONDS))
    jax.profiler.start_trace(str(trace_dir))
    try:
        time.sleep(TRACE_SECONDS)
    finally:
        jax.profiler.stop_trace()
    th.join()
    return secs


def traced_call(system, trace_dir: Path, expected_s: float) -> dict | None:
    """Trace the boundary between two more experiment calls
    (:func:`capture`) and reduce it with :func:`reduce_split`, adding
    the seconds spent lowering the programs' HLO (``hlo_s``, once per
    run) and reducing (``reduce_s``).  The trace files are deleted once
    read."""
    from bench import phases
    capture(system, trace_dir, expected_s)
    t, lowering = time.perf_counter(), []

    def hlo():
        a = time.perf_counter()
        try:
            return phases.compiled_hlo()
        finally:
            lowering.append(time.perf_counter() - a)

    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    try:
        planes = phases.load(files[0], hlo=hlo)[0] if files else []
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = reduce_split(planes, len(system.specs))
    if out is not None:
        out["hlo_s"] = sum(lowering)
        out["reduce_s"] = time.perf_counter() - t - out["hlo_s"]
    return out
