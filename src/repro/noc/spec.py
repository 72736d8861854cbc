"""Declarative NoC experiment specification.

A :class:`NocSpec` declares *what the network is* — a first-class
:class:`~repro.noc.topology.Topology` (XY mesh, torus, express-link
mesh), a :class:`~repro.noc.routing.RoutingPolicy` (routing algorithm
x virtual-channel count; the default ``RoutingPolicy.xy(n_vcs=1)`` is
the paper's plain VC-less XY configuration, bit-identical to the
pre-VC engine), an arbitrary list of physical channels (each its own
complete network instance of that topology), the traffic classes
riding on them, and a ``class_map`` assigning every AXI4 flow to a
channel.  Each class decomposes into the five AXI
channels (:data:`repro.core.flit.AXI_FLOWS`): reads are
``"<class>.ar"`` -> ``"<class>.r"``, writes are ``"<class>.aw"`` ->
``"<class>.w"`` -> ``"<class>.b"``.  The paper's mapping puts the
single-flit address/ack flows (AW / AR / B) on the narrow channels and
the data bursts (W / R) on the wide one.  Legacy two-flow maps
(``"<class>.req"`` / ``"<class>.rsp"``) are expanded automatically:
``req`` covers AR + AW, ``rsp`` covers R + B, and W rides the class's
R (data) channel.  The paper's two configurations are presets:

* :meth:`NocSpec.narrow_wide` — three physical networks (narrow_req /
  narrow_rsp / wide), paper §III-B Table I,
* :meth:`NocSpec.wide_only` — the Fig. 5 ablation where one network
  carries everything,

but any N-channel topology can be declared, e.g. the journal version's
end-to-end parallel multi-stream wide channels or PATRONoC-style
per-stream links.

Everything here is frozen/hashable: a ``NocSpec`` is the static cache
key for one jitted simulator (see ``engine.py``); the *dynamic* knobs
(service latency, outstanding limits, burst lengths, schedules) are
traced operands so sweeps vmap over them.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Mapping, Sequence

from repro.core.flit import AXI_FLOWS
from .faults import FaultModel
from .routing import RoutingPolicy
from .topology import Mesh, Topology, Torus  # noqa: F401  (re-exported)


@dataclass(frozen=True)
class TrafficClass:
    """One AXI4 traffic class (paper: narrow vs wide).

    ``burst_beats == 1`` marks a latency-critical class whose data
    bursts (R read data, W write data) are single flits; ``burst_beats
    > 1`` marks a bandwidth class whose bursts are atomic wormhole
    trains of that many beats.  ``max_outstanding`` bounds reads and
    writes *separately* (one ROB budget per direction, paper §III-A).

    ``service_lat`` / ``service_jitter`` give the class its own target
    service-latency *distribution*: the target NI answers a request
    after ``service_lat + U[-jitter, +jitter]`` cycles (offsets come
    from a seeded static table so runs are reproducible; both knobs are
    traced operands at simulate() time).  ``service_lat=None`` falls
    back to the spec-wide :attr:`NocSpec.service_lat` scalar, and
    ``service_jitter=0`` reproduces the fixed-latency model exactly.

    ``n_streams`` is the journal version's end-to-end AXI4 parallel
    multi-stream support: the class's transactions are spread over that
    many independent AXI ID streams, each with its own schedule pointer,
    its own slice of the ``max_outstanding`` credits (split as evenly as
    integer division allows, earlier streams get the remainder) and its
    own ROB/reorder slots — so a slow transaction on one stream never
    false-serializes traffic on another.  ``n_streams=1`` (the default)
    is the single-ID behaviour, bit-identical to the pre-stream engine.
    """
    name: str
    burst_beats: int = 1
    max_outstanding: int = 8       # per-direction ROB budget (all streams)
    payload_bits: int = 64         # per-beat payload (accounting only)
    service_lat: int | None = None   # None -> NocSpec.service_lat
    service_jitter: int = 0          # +/- uniform jitter, 0 = deterministic
    n_streams: int = 1               # independent AXI ID streams


@dataclass(frozen=True)
class PhysicalChannel:
    """One physical network instance (a complete router mesh; the
    spec-level :class:`~repro.noc.routing.RoutingPolicy` decides how
    many virtual channels each of its links carries)."""
    name: str
    depth: int = 2                 # input FIFO depth per router port
    width_bits: int = 603          # link width incl. header lines (accounting)


def _resolve_topology(nx: int, ny: int,
                      topology: "Topology | None") -> "Topology":
    """Preset helper: default to the paper's mesh; an explicit override
    must agree with the nx/ny arguments so a sweep can't silently
    simulate the wrong fabric."""
    if topology is None:
        return Mesh(nx, ny)
    if (topology.nx, topology.ny) != (nx, ny):
        raise ValueError(
            f"topology {topology!r} does not match nx={nx}, ny={ny}")
    return topology


@dataclass(frozen=True)
class NocSpec:
    """Static description of one NoC experiment configuration.

    ``topology`` is a first-class value (:class:`Mesh`, :class:`Torus`,
    or ``Mesh(..., express=...)`` for >5-port express-link routers) —
    every physical channel is one complete network instance of it.
    ``routing`` selects the routing algorithm and virtual-channel count
    every channel runs (:class:`~repro.noc.routing.RoutingPolicy`); the
    default single-VC XY policy reproduces the pre-VC engine
    bit-for-bit, while e.g. ``RoutingPolicy.xy(n_vcs=2)`` enables the
    dateline/escape-VC discipline that makes the torus deadlock-free.
    """
    topology: Topology = Mesh(4, 4)
    classes: tuple[TrafficClass, ...] = (
        TrafficClass("narrow", burst_beats=1, max_outstanding=8,
                     payload_bits=64),
        TrafficClass("wide", burst_beats=16, max_outstanding=8,
                     payload_bits=512),
    )
    channels: tuple[PhysicalChannel, ...] = (
        PhysicalChannel("req", depth=2, width_bits=119),
        PhysicalChannel("rsp", depth=2, width_bits=103),
        PhysicalChannel("wide", depth=2, width_bits=603),
    )
    # flow ("<class>.<ar|r|aw|w|b>") -> channel name, stored sorted.
    # Legacy "<class>.req"/"<class>.rsp" entries are expanded (req ->
    # AR+AW, rsp -> R+B, W rides the R data channel).  Default: the
    # paper's narrow_wide mapping — AW/AR/B narrow, W/R wide for the
    # wide class, everything narrow for the narrow class.
    class_map: tuple[tuple[str, str], ...] = (
        ("narrow.ar", "req"), ("narrow.aw", "req"), ("narrow.w", "req"),
        ("narrow.r", "rsp"), ("narrow.b", "rsp"),
        ("wide.ar", "req"), ("wide.aw", "req"), ("wide.b", "rsp"),
        ("wide.w", "wide"), ("wide.r", "wide"),
    )
    service_lat: int = 10          # target memory + NI latency (cycles)
    cycles: int = 4000
    # per-NI response reorder-ring capacity (entries per queue).  Sizes
    # the engine's field-major (6, n_rq, resp_q_cap, R) ring state (an
    # entry is written once, at push; an (R, n_q) counter beside the
    # head/tail pointers tracks the head's sent beats), so small
    # studies can shrink it; must cover the worst-case R+B responses
    # pending at one NI (bounded by sum over classes of max_outstanding
    # x #sources targeting it — the engine does not check overflow at
    # runtime).  The per-class W rings are sized separately from the
    # classes' declared max_outstanding.
    resp_q_cap: int = 256
    # routing algorithm x VC count (kept after the scalar knobs so
    # older positional constructions stay valid).  Validated against
    # the topology below.
    routing: RoutingPolicy = RoutingPolicy()
    # fault-injection + NI robustness model (new last field, same
    # positional-compatibility rule).  None = the healthy fabric with
    # the fault machinery entirely compiled out (bit-identical to the
    # pre-fault engine); see repro.noc.faults.FaultModel.
    faults: FaultModel | None = None

    def __post_init__(self):
        if not isinstance(self.resp_q_cap, int) or isinstance(
                self.resp_q_cap, bool) or self.resp_q_cap < 2:
            raise ValueError(
                f"resp_q_cap must be an int >= 2, got {self.resp_q_cap!r}")
        if not (callable(getattr(self.topology, "tables", None))
                and getattr(self.topology, "__hash__", None)):
            raise TypeError(
                f"topology must be a hashable Topology (Mesh/Torus) with "
                f"static tables(), got {self.topology!r}")
        if not isinstance(self.routing, RoutingPolicy):
            raise TypeError(
                f"routing must be a RoutingPolicy, got {self.routing!r}")
        self.routing.validate_for(self.topology)
        if self.faults is not None:
            if not isinstance(self.faults, FaultModel):
                raise TypeError(
                    f"faults must be a FaultModel or None, got "
                    f"{self.faults!r}")
            R = self.topology.n_routers
            ids = ({n for n in self.faults.dead_nodes}
                   | {i for lk in self.faults.dead_links for i in lk}
                   | {i for ev in self.faults.link_events for i in ev[:2]})
            if ids and max(ids) >= R:
                raise ValueError(
                    f"fault references router {max(ids)}, but "
                    f"{self.topology!r} has only {R} routers")
            if self.faults.has_static and self.faults.reroute:
                # cheap static preconditions of the cut-out reroute;
                # the unroutable-cut case needs tables and is raised
                # (or reported by analyze) at compile time instead
                if self.routing.algorithm != "xy":
                    raise ValueError(
                        f"static fault reroute supports algorithm='xy' "
                        f"only, got {self.routing.algorithm!r}")
                need = self.routing.required_vcs(self.topology) + 1
                if self.routing.n_vcs < need:
                    raise ValueError(
                        f"static fault reroute on {self.topology!r} "
                        f"needs n_vcs >= {need} (base discipline + one "
                        f"dedicated detour VC), got {self.routing.n_vcs}")
            tc = self.faults.timeout_cycles
            if not isinstance(tc, int) and len(tc) != len(self.classes):
                raise ValueError(
                    f"per-class timeout_cycles has {len(tc)} entries for "
                    f"{len(self.classes)} classes")
        if isinstance(self.classes, Sequence) and not isinstance(
                self.classes, tuple):
            object.__setattr__(self, "classes", tuple(self.classes))
        if isinstance(self.channels, Sequence) and not isinstance(
                self.channels, tuple):
            object.__setattr__(self, "channels", tuple(self.channels))
        cm = self.class_map
        items = list(cm.items()) if isinstance(cm, Mapping) else list(cm)
        if len({k for k, _ in items}) != len(items):
            raise ValueError("class_map has duplicate flow entries")
        items = self._expand_legacy(items)
        # normalize (sort) regardless of input form so equivalent specs
        # hash equal and share one compiled simulator
        cm = tuple(sorted(items))
        object.__setattr__(self, "class_map", cm)
        names = {c.name for c in self.classes}
        chans = {c.name for c in self.channels}
        if len(names) != len(self.classes):
            raise ValueError("duplicate traffic class names")
        if len(chans) != len(self.channels):
            raise ValueError("duplicate channel names")
        for ch in self.channels:
            if ch.depth < 1:
                raise ValueError(
                    f"channel {ch.name!r} needs FIFO depth >= 1, got "
                    f"{ch.depth}")
        for cls in self.classes:
            if cls.service_lat is not None and cls.service_lat < 0:
                raise ValueError(
                    f"class {cls.name!r} service_lat must be >= 0")
            if cls.service_jitter < 0:
                raise ValueError(
                    f"class {cls.name!r} service_jitter must be >= 0")
            if not isinstance(cls.n_streams, int) or isinstance(
                    cls.n_streams, bool) or not (
                    1 <= cls.n_streams <= cls.max_outstanding):
                raise ValueError(
                    f"class {cls.name!r} n_streams must be an int in "
                    f"[1, max_outstanding={cls.max_outstanding}], got "
                    f"{cls.n_streams!r}")
        flows = dict(cm)
        for cls in self.classes:
            for d in AXI_FLOWS:
                flow = f"{cls.name}.{d}"
                if flow not in flows:
                    raise ValueError(f"class_map missing flow {flow!r}")
                if flows[flow] not in chans:
                    raise ValueError(
                        f"flow {flow!r} mapped to unknown channel "
                        f"{flows[flow]!r}")
        for flow in flows:
            cls_name, _, d = flow.partition(".")
            if cls_name not in names or d not in AXI_FLOWS:
                raise ValueError(f"class_map has unknown flow {flow!r}")
        # cheap static verification (repro.noc.analyze protocol/credit
        # checks; lazy import — analyze depends on this module): a FAIL,
        # e.g. a resp_q_cap that a single class's ROB budget can
        # overflow, rejects the spec at construction.  WARNs stay
        # advisory, and the expensive channel-dependency deadlock pass
        # waits for analyze()/simulate(verify="full").
        from .analyze import verify_spec
        verify_spec(self, "fast")

    @staticmethod
    def _expand_legacy(items: list[tuple[str, str]]) -> list[tuple[str, str]]:
        """Expand legacy ``"<cls>.req"``/``"<cls>.rsp"`` entries into the
        five AXI flows: req carries the address flows (AR, AW), rsp the
        response flows (R, B), and W rides the class's R data channel —
        W and R are the payload pair the paper puts on the wide link."""
        if not any(k.endswith((".req", ".rsp")) for k, _ in items):
            return items
        explicit = {k for k, _ in items
                    if not k.endswith((".req", ".rsp"))}
        out, rsp_ch = [], {}
        for k, ch in items:
            cls_name, _, d = k.partition(".")
            if d == "req":
                out += [(f"{cls_name}.{f}", ch) for f in ("ar", "aw")
                        if f"{cls_name}.{f}" not in explicit]
            elif d == "rsp":
                out += [(f"{cls_name}.{f}", ch) for f in ("r", "b")
                        if f"{cls_name}.{f}" not in explicit]
                rsp_ch[cls_name] = ch
            else:
                out.append((k, ch))
        have = {k for k, _ in out}
        for cls_name, ch in rsp_ch.items():
            if f"{cls_name}.w" not in have:
                out.append((f"{cls_name}.w", ch))
        return out

    # ------------------------------------------------------------------ #
    @property
    def nx(self) -> int:
        return self.topology.nx

    @property
    def ny(self) -> int:
        return self.topology.ny

    @property
    def n_routers(self) -> int:
        return self.topology.n_routers

    @property
    def flow_map(self) -> dict[str, str]:
        return dict(self.class_map)

    def class_index(self, name: str) -> int:
        for i, c in enumerate(self.classes):
            if c.name == name:
                return i
        raise KeyError(name)

    def get_class(self, name: str) -> TrafficClass:
        return self.classes[self.class_index(name)]

    def channel_index(self, name: str) -> int:
        for i, c in enumerate(self.channels):
            if c.name == name:
                return i
        raise KeyError(name)

    def flow_channel(self, cls_name: str, flow: str) -> int:
        """Channel index carrying ``cls_name``'s AXI ``flow``."""
        if flow not in AXI_FLOWS:
            raise KeyError(f"unknown AXI flow {flow!r}; have {AXI_FLOWS}")
        return self.channel_index(self.flow_map[f"{cls_name}.{flow}"])

    def req_channel(self, cls_name: str) -> int:
        """Legacy alias: the channel carrying the class's AR flow."""
        return self.flow_channel(cls_name, "ar")

    def rsp_channel(self, cls_name: str) -> int:
        """Legacy alias: the channel carrying the class's R flow."""
        return self.flow_channel(cls_name, "r")

    @property
    def burstlen(self) -> int:
        """Largest declared burst (legacy traffic generators key off it)."""
        return max(c.burst_beats for c in self.classes)

    def with_(self, **kw) -> "NocSpec":
        return replace(self, **kw)

    # ---------------------------------------------------------------- #
    # paper presets
    # ---------------------------------------------------------------- #
    @classmethod
    def narrow_wide(cls, nx: int = 4, ny: int = 4, *,
                    topology: Topology | None = None, depth: int = 2,
                    burstlen: int = 16, service_lat: int = 10,
                    cycles: int = 4000, max_narrow_outstanding: int = 8,
                    max_wide_outstanding: int = 8,
                    resp_q_cap: int = 256,
                    routing: RoutingPolicy | None = None,
                    faults: FaultModel | None = None) -> "NocSpec":
        """Paper §III-B: three independent physical networks, with the
        AXI flows mapped per the paper — single-flit address/ack flows
        (AR, AW, B) plus the narrow class's data on the narrow req/rsp
        pair, wide W/R data bursts on the wide channel.

        ``topology`` overrides the default XY mesh (e.g. ``Torus(nx,
        ny)`` or ``Mesh(nx, ny, express=(2,))``); ``routing``
        overrides the default single-VC XY policy (e.g.
        ``RoutingPolicy.xy(n_vcs=2)`` for a deadlock-free torus)."""
        return cls(
            topology=_resolve_topology(nx, ny, topology),
            classes=(
                TrafficClass("narrow", 1, max_narrow_outstanding, 64),
                TrafficClass("wide", burstlen, max_wide_outstanding, 512),
            ),
            channels=(
                PhysicalChannel("req", depth, 119),
                PhysicalChannel("rsp", depth, 103),
                PhysicalChannel("wide", depth, 603),
            ),
            class_map=(
                ("narrow.ar", "req"), ("narrow.aw", "req"),
                ("narrow.w", "req"),
                ("narrow.r", "rsp"), ("narrow.b", "rsp"),
                ("wide.ar", "req"), ("wide.aw", "req"),
                ("wide.b", "rsp"),
                ("wide.w", "wide"), ("wide.r", "wide")),
            service_lat=service_lat, cycles=cycles, resp_q_cap=resp_q_cap,
            routing=RoutingPolicy() if routing is None else routing,
            faults=faults)

    @classmethod
    def wide_only(cls, nx: int = 4, ny: int = 4, *,
                  topology: Topology | None = None, depth: int = 2,
                  burstlen: int = 16, service_lat: int = 10,
                  cycles: int = 4000, max_narrow_outstanding: int = 8,
                  max_wide_outstanding: int = 8,
                  resp_q_cap: int = 256,
                  routing: RoutingPolicy | None = None,
                  faults: FaultModel | None = None) -> "NocSpec":
        """Fig. 5 ablation: ONE network carries all five flows of every
        class; narrow flits burn full wide-link cycles and bursts hold
        links end-to-end."""
        return cls(
            topology=_resolve_topology(nx, ny, topology),
            classes=(
                TrafficClass("narrow", 1, max_narrow_outstanding, 64),
                TrafficClass("wide", burstlen, max_wide_outstanding, 512),
            ),
            channels=(PhysicalChannel("wide", depth, 603),),
            class_map=tuple((f"{c}.{f}", "wide")
                            for c in ("narrow", "wide")
                            for f in AXI_FLOWS),
            service_lat=service_lat, cycles=cycles, resp_q_cap=resp_q_cap,
            routing=RoutingPolicy() if routing is None else routing,
            faults=faults)

    @classmethod
    def multi_stream(cls, nx: int = 4, ny: int = 4, *, n_wide: int = 2,
                     topology: Topology | None = None,
                     depth: int = 2, burstlen: int = 16,
                     service_lat: int = 10, cycles: int = 4000,
                     resp_q_cap: int = 256,
                     routing: RoutingPolicy | None = None,
                     faults: FaultModel | None = None) -> "NocSpec":
        """Journal-version style: ``n_wide`` parallel wide stream channels
        (wide class i's W/R data bursts ride their own physical network)
        next to the shared narrow req/rsp pair carrying every class's
        AR/AW address flows and B acks."""
        classes = [TrafficClass("narrow", 1, 8, 64)]
        channels = [PhysicalChannel("req", depth, 119),
                    PhysicalChannel("rsp", depth, 103)]
        cmap = [("narrow.ar", "req"), ("narrow.aw", "req"),
                ("narrow.w", "req"),
                ("narrow.r", "rsp"), ("narrow.b", "rsp")]
        for i in range(n_wide):
            classes.append(TrafficClass(f"wide{i}", burstlen, 8, 512))
            channels.append(PhysicalChannel(f"wide{i}", depth, 603))
            cmap += [(f"wide{i}.ar", "req"), (f"wide{i}.aw", "req"),
                     (f"wide{i}.b", "rsp"),
                     (f"wide{i}.w", f"wide{i}"), (f"wide{i}.r", f"wide{i}")]
        return cls(topology=_resolve_topology(nx, ny, topology),
                   classes=tuple(classes), channels=tuple(channels),
                   class_map=tuple(sorted(cmap)),
                   service_lat=service_lat, cycles=cycles,
                   resp_q_cap=resp_q_cap,
                   routing=RoutingPolicy() if routing is None else routing,
                   faults=faults)
