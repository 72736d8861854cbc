"""Seconds of set-up in the program's ``noc.build`` span (simulator,
trace, lowering, compilation or persistent-cache load), from its
``span_stats()`` table read after the warm call; nothing where the
program recorded no such span."""


def read(run):
    build = run.get("setup_spans", {}).get("noc.build")
    return None if build is None else build["seconds"]
