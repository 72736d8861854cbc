"""Plain reference of the FlooNoC cycle model, in numpy.

It simulates, cycle by cycle, what a configuration file states: an XY
mesh, one or more physical networks (each a complete mesh of
input-buffered, two-cycle routers with round-robin output arbitration
and wormhole-locked bursts), and AXI4 network interfaces (reads
AR -> R, writes AW -> W -> B, a read and a write ROB budget per class,
a fixed service latency at the target).  It imports nothing of the
program under test and reads nothing the program made: its inputs are
the configuration's JSON and the schedules of ``bench/traffic.py``.

Written for clarity over speed, but vectorised over (points, routers)
so that the benchmark's cells can be checked in seconds: one 7x7
experiment of 8000 cycles, or one 32x32 experiment of 4000 cycles.

Semantics, cycle ``now``, everything read from the cycle-start state:

* NI issue: each class's next scheduled transaction is due when its
  time has come; a read needs a free read credit, a write a free
  write credit (``max_outstanding`` each).
* NI injection, per physical network, by which flows it carries:
  a network that carries only one response ring streams that ring's
  head; a network with request flows only takes a started W burst
  first, then address flits (single-beat classes first), then a fresh
  W burst; a network carrying both round-robins over [response
  rings..., classes...], a started burst holding the network.
* Router: an output register drains when it is valid and the
  downstream FIFO has room (the Local output always drains into the
  NI); each output register that is free (or draining) takes the
  round-robin winner among the input heads routed to it, a wormhole
  lock keeping it for the burst's input until the tail beat.
* NI delivery: an AR at the target queues an R burst, the last W beat
  queues a B, each ready ``service_lat`` cycles later; an R tail or a
  B at the source completes the transaction and frees its credit.  An
  AW that enters the network queues its W burst, ready next cycle.

``depth_offset`` breaks the link-level back-pressure guarantee (every
FIFO accepts that many flits more than the configuration states): the
control that the comparison has to reject.
"""
from __future__ import annotations

import numpy as np

BIG = 1 << 30
FLOWS = ("ar", "r", "aw", "w", "b")
N, E, S, W, L = range(5)          # router ports; Local is last
P = 5
NO_PORT = 99
DEST, SRC, TIME, KIND, TXN, BEAT = range(6)          # flit fields
READY, QDEST, QBEATS, QTIME, QTXN, QKIND = range(6)  # queue-entry fields


def kind(lane: int, flow: str) -> int:
    return len(FLOWS) * lane + FLOWS.index(flow)


def mesh_xy(nx: int, ny: int):
    """(nbr, opp, route, feed_r, feed_o) of an nx x ny XY mesh with the
    port order N, E, S, W, Local."""
    R = nx * ny
    x, y = np.arange(R) % nx, np.arange(R) // nx
    step = {N: (0, -1), E: (1, 0), S: (0, 1), W: (-1, 0)}
    back = {N: S, E: W, S: N, W: E}
    nbr = np.full((R, P), -1)
    opp = np.full((R, P), L)
    feed_r = np.full((R, P), -1)
    feed_o = np.full((R, P), -1)
    for p, (dx, dy) in step.items():
        tx, ty = x + dx, y + dy
        on = (0 <= tx) & (tx < nx) & (0 <= ty) & (ty < ny)
        t = (ty * nx + tx)[on]
        nbr[on, p], opp[on, p] = t, back[p]
        feed_r[t, back[p]], feed_o[t, back[p]] = np.flatnonzero(on), p
    dx = x[None, :] - x[:, None]                  # (router, destination)
    dy = y[None, :] - y[:, None]
    route = np.where(dx > 0, E, np.where(dx < 0, W, np.where(
        dy > 0, S, np.where(dy < 0, N, L))))
    return nbr, opp, route, feed_r, feed_o


class Plan:
    """Which flows each physical network carries, from the
    configuration's ``class_map``."""

    def __init__(self, cfg: dict):
        self.classes = cfg["classes"]
        self.channels = [c["name"] for c in cfg["channels"]]
        n_ln = len(self.classes)
        cmap = cfg["class_map"]
        self.ch = {f: [self.channels.index(cmap[f"{c['name']}.{f}"])
                       for c in self.classes] for f in FLOWS}
        ring_ch = []
        for c in self.ch["r"] + self.ch["b"]:
            if c not in ring_ch:
                ring_ch.append(c)
        self.n_rq = len(ring_ch)
        self.n_q = self.n_rq + n_ln
        self.rq_of_r = [ring_ch.index(c) for c in self.ch["r"]]
        self.rq_of_b = [ring_ch.index(c) for c in self.ch["b"]]
        self.chan_of_q = ring_ch + self.ch["w"]
        prio = sorted(range(n_ln),
                      key=lambda i: (self.classes[i]["burst_beats"] > 1, i))
        n_ch = len(self.channels)
        self.singles_on = [[(i, f) for i in prio for f in ("ar", "aw")
                            if self.ch[f][i] == c] for c in range(n_ch)]
        self.wqs_on = [[self.n_rq + i for i in prio if self.ch["w"][i] == c]
                       for c in range(n_ch)]
        self.rqs_on = [[q for q in range(self.n_rq) if ring_ch[q] == c]
                       for c in range(n_ch)]
        self.rr_lanes = [[i for i in prio
                          if c in (self.ch["ar"][i], self.ch["aw"][i],
                                   self.ch["w"][i])] for c in range(n_ch)]
        self.push_order_r = [i for c in range(n_ch) for i in prio
                             if self.ch["ar"][i] == c]


def simulate(cfg: dict, times, dests, writes, depths=None,
             depth_offset: int = 0) -> list[dict]:
    """Run ``B`` experiments of one configuration side by side.

    ``times``/``dests``/``writes`` are (B, L, R, T) per class schedules
    (``times >= BIG`` marks an empty slot); ``depths`` is (B, n_ch),
    the FIFO depth of each network in each experiment (default: the
    configuration's).  Returns one flat dict per experiment, keyed as
    ``bench.compare.flatten`` keys a ``SimResult``."""
    topo = cfg["topology"]
    if (topo["kind"] != "mesh" or topo.get("routing", "xy") != "xy"
            or topo.get("n_vcs", 1) != 1 or topo.get("express")):
        raise ValueError(f"the reference models single-VC XY meshes, not "
                         f"{topo}")
    if any(c.get("n_streams", 1) != 1 or c.get("service_jitter", 0)
           for c in cfg["classes"]):
        raise ValueError("the reference models one AXI ID stream and a "
                         "fixed service latency per class")
    nx, ny = topo["nx"], topo["ny"]
    R = nx * ny
    plan = Plan(cfg)
    n_ln, n_ch, n_q = len(plan.classes), len(plan.channels), plan.n_q
    times = np.asarray(times, np.int64)
    dests = np.asarray(dests, np.int64)
    writes = np.asarray(writes, np.int64)
    Bn, _, _, T = times.shape
    if depths is None:
        depths = [[c["depth"] for c in cfg["channels"]]] * Bn
    depth = np.asarray(depths, np.int64) + depth_offset       # (B, C)
    D = int(depth.max())
    cap = cfg["resp_q_cap"]
    sl = np.asarray([c.get("service_lat", cfg["service_lat"])
                     for c in plan.classes])
    mo = np.asarray([c["max_outstanding"] for c in plan.classes])
    bb = np.asarray([c["burst_beats"] for c in plan.classes])
    cycles = cfg["cycles"]
    nbr, opp, route, feed_r, feed_o = mesh_xy(nx, ny)
    has_feed = feed_r >= 0
    fr, fo = np.clip(feed_r, 0, None), np.clip(feed_o, 0, None)
    nb = np.clip(nbr, 0, None)
    is_local = np.arange(P) == L
    rows = np.arange(R)
    dep4 = depth[:, :, None, None]

    # schedules as (B, R, L, T)
    tt = times.transpose(0, 2, 1, 3)
    dd = dests.transpose(0, 2, 1, 3)
    ww = writes.transpose(0, 2, 1, 3)

    # fabric state, (B, C, R, P, ...)
    fifo = np.zeros((Bn, n_ch, R, P, D, 6), np.int64)
    count = np.zeros((Bn, n_ch, R, P), np.int64)
    rr = np.zeros((Bn, n_ch, R, P), np.int64)
    oreg = np.zeros((Bn, n_ch, R, P, 6), np.int64)
    oreg_v = np.zeros((Bn, n_ch, R, P), bool)
    lock = np.full((Bn, n_ch, R, P), -1, np.int64)
    # NI state, (B, R, lanes) and (B, R, queues)
    z = np.zeros((Bn, R, n_ln), np.int64)
    ptr, out_r, out_w = z.copy(), z.copy(), z.copy()
    ring = np.zeros((Bn, R, n_q, cap, 6), np.int64)
    head = np.zeros((Bn, R, n_q), np.int64)
    tail = np.zeros((Bn, R, n_q), np.int64)
    started = np.zeros((Bn, R, n_q), bool)
    inj_rr = np.zeros((Bn, R, n_ch), np.int64)
    m = {k: z.copy() for k in ("done", "lat_sum", "lat_max", "beats_rx",
                               "last_t", "w_done", "w_lat_sum",
                               "w_lat_max", "w_beats_rx", "w_last_t")}
    m["first_t"] = np.full((Bn, R, n_ln), BIG, np.int64)
    m["w_first_t"] = np.full((Bn, R, n_ln), BIG, np.int64)
    moves = np.zeros((Bn, n_ch), np.int64)
    occ_sum = np.zeros((Bn, n_ch), np.int64)
    occ_max = np.zeros((Bn, n_ch), np.int64)
    cur_stall = np.zeros(Bn, np.int64)
    max_stall = np.zeros(Bn, np.int64)
    bi = np.arange(Bn)[:, None, None]
    ri = rows[None, :, None]
    qi = np.arange(n_q)[None, None, :]

    for now in range(cycles):
        # ---- NI: due transactions and queue heads ------------------------
        p = np.clip(ptr, 0, T - 1)[..., None]
        t_sel = np.take_along_axis(tt, p, 3)[..., 0]
        is_wr = np.take_along_axis(ww, p, 3)[..., 0] > 0
        req_d = np.take_along_axis(dd, p, 3)[..., 0]
        due = (ptr < T) & (t_sel <= now)
        want = {"ar": due & ~is_wr & (out_r < mo),
                "aw": due & is_wr & (out_w < mo)}
        h = ring[bi, ri, qi, head % cap]                     # (B, R, Q, 6)
        have = head < tail
        h_ready = have & (h[..., READY] <= now)
        h_held = started & (h[..., QBEATS] > 0)

        # ---- NI: what each network's NI port offers -----------------------
        sel = {}                    # ("ar"|"aw", lane) / ("q", ring) -> mask
        hold = {}
        iv = np.zeros((Bn, n_ch, R), bool)
        iflit = np.zeros((Bn, n_ch, R, 6), np.int64)
        for c in range(n_ch):
            pick = []               # (key, mask, flit), in priority order
            singles, wqs, rqs = (plan.singles_on[c], plan.wqs_on[c],
                                 plan.rqs_on[c])

            def head_flit(q):
                return (h[:, :, q, QDEST], h[:, :, q, QKIND],
                        h[:, :, q, QTXN], h[:, :, q, QTIME],
                        h[:, :, q, QBEATS])

            def single_flit(i, f):
                return (req_d[:, :, i], kind(i, f), ptr[:, :, i], now, 1)

            if not singles and not wqs and len(rqs) == 1:
                q = rqs[0]
                pick.append((("q", q), h_ready[:, :, q], head_flit(q)))
            elif not rqs:
                taken = np.zeros((Bn, R), bool)
                for q in wqs:
                    s = h_held[:, :, q] & ~taken
                    taken |= s
                    pick.append((("q", q), s, head_flit(q)))
                for i, f in singles:
                    s = want[f][:, :, i] & ~taken
                    taken |= s
                    pick.append(((f, i), s, single_flit(i, f)))
                for q in wqs:
                    s = h_ready[:, :, q] & ~taken
                    taken |= s
                    pick.append((("q", q), s, head_flit(q)))
            elif singles or wqs or rqs:
                lanes = plan.rr_lanes[c]
                cands = [("q", q) for q in rqs] + [("lane", i) for i in lanes]
                n_c = len(cands)

                def lane_valid(i):
                    v = np.zeros((Bn, R), bool)
                    for f in ("ar", "aw"):
                        if plan.ch[f][i] == c:
                            v |= want[f][:, :, i]
                    if plan.ch["w"][i] == c:
                        v |= h_ready[:, :, plan.n_rq + i]
                    return v

                valid = np.stack([h_ready[:, :, q] for q in rqs]
                                 + [lane_valid(i) for i in lanes], -1)
                start = inj_rr[:, :, c] % n_c
                choice = np.full((Bn, R), -1)
                for k in range(n_c - 1, -1, -1):     # first valid from start
                    j = (start + k) % n_c
                    v = np.take_along_axis(valid, j[..., None], -1)[..., 0]
                    choice = np.where(v, j, choice)
                held = np.zeros((Bn, R), bool)
                for k, (tag, idx) in enumerate(cands):
                    q = idx if tag == "q" else plan.n_rq + idx
                    if tag == "lane" and plan.ch["w"][idx] != c:
                        continue
                    hq = h_held[:, :, q]
                    choice = np.where(hq & ~held, k, choice)
                    held |= hq
                hold[c] = held
                for k, (tag, idx) in enumerate(cands):
                    s_slot = choice == k
                    if tag == "q":
                        pick.append((("q", idx), s_slot & h_ready[:, :, idx],
                                     head_flit(idx)))
                        continue
                    i, taken = idx, np.zeros((Bn, R), bool)
                    wq = plan.n_rq + i if plan.ch["w"][i] == c else None
                    if wq is not None:
                        s = s_slot & h_held[:, :, wq]
                        taken |= s
                        pick.append((("q", wq), s, head_flit(wq)))
                    for f in ("ar", "aw"):
                        if plan.ch[f][i] == c:
                            s = s_slot & want[f][:, :, i] & ~taken
                            taken |= s
                            pick.append(((f, i), s, single_flit(i, f)))
                    if wq is not None:
                        s = s_slot & h_ready[:, :, wq] & ~taken
                        taken |= s
                        pick.append((("q", wq), s, head_flit(wq)))
            for key, s, (fd, fk, ftx, ftm, fbt) in pick:
                sel[key] = sel.get(key, False) | s
                iv[:, c] |= s
                for fld, val in ((DEST, fd), (SRC, rows[None, :]),
                                 (TIME, ftm), (KIND, fk), (TXN, ftx),
                                 (BEAT, fbt)):
                    iflit[:, c, :, fld] = np.where(s, val,
                                                   iflit[:, c, :, fld])

        # ---- routers: drain, neighbour push, injection, route ------------
        ds = count[:, :, nb, opp]
        can = np.where(is_local, True, (nbr >= 0) & (ds < dep4))
        drain = oreg_v & can
        recv_v = has_feed & drain[:, :, fr, fo]
        inj_ok = iv & (count[..., L] < depth[:, :, None])
        recv_v[..., L] = inj_ok
        step_moves = drain[..., :L].sum((2, 3))
        dv, dflit = drain[..., L], oreg[..., L, :].copy()
        dst_in = np.flatnonzero(recv_v)               # flat input ports
        bc_in, r_in, p_in = dst_in // (R * P), dst_in // P % R, dst_in % P
        feeder = (bc_in * R + fr[r_in, p_in]) * P + fo[r_in, p_in]
        flit_in = np.where((p_in == L)[:, None],
                           iflit.reshape(-1, 6)[dst_in // P],
                           oreg.reshape(-1, 6)[feeder])

        # ---- routers: round-robin arbitration into free output registers -
        # (flat over (point, network, router, port): only occupied input
        # FIFOs request, so the work follows the traffic)
        fl_count, fl_fifo = count.reshape(-1), fifo.reshape(-1, D, 6)
        fl_rr, fl_lock = rr.reshape(-1), lock.reshape(-1)
        free = (~oreg_v | drain).reshape(-1)
        src = np.flatnonzero(fl_count > 0)            # occupied inputs
        bcr, i_in = src // P, src % P
        hflit = fl_fifo[src, 0]
        o_out = route[bcr % R, hflit[:, DEST]]
        dst = bcr * P + o_out                         # requested output
        lk = fl_lock[dst]
        ok_req = free[dst] & ((lk < 0) | (lk == i_in))
        src, i_in, hflit, dst = src[ok_req], i_in[ok_req], hflit[ok_req], \
            dst[ok_req]
        prio = (i_in - fl_rr[dst]) % P
        best = np.full(fl_count.size, NO_PORT)
        np.minimum.at(best, dst, prio)
        win = best[dst] == prio                       # one per output
        src, i_in, hflit, dst = src[win], i_in[win], hflit[win], dst[win]
        # output registers: drained ones empty, granted ones refill
        fl_oreg_v = oreg_v.reshape(-1) & ~drain.reshape(-1)
        fl_oreg_v[dst] = True
        oreg_v = fl_oreg_v.reshape(oreg_v.shape)
        fl_oreg = oreg.reshape(-1, 6)
        fl_oreg[dst] = hflit
        unlocked = fl_lock[dst] < 0
        fl_rr[dst[unlocked]] = (i_in[unlocked] + 1) % P
        fl_lock[dst] = np.where(hflit[:, BEAT] > 1, i_in, -1)
        # pop the granted heads, then push what arrived
        fl_fifo[src, :-1] = fl_fifo[src, 1:]
        fl_count[src] -= 1
        room = fl_count[dst_in] < depth.reshape(-1)[bc_in]
        dst_in, flit_in = dst_in[room], flit_in[room]
        fl_fifo[dst_in, fl_count[dst_in]] = flit_in
        fl_count[dst_in] += 1

        # ---- NI: what was injected -------------------------------------
        ok = inj_ok                                          # (B, C, R)
        zero = np.zeros((Bn, R), bool)
        inj = {f: np.stack([ok[:, plan.ch[f][i]] & sel.get((f, i), zero)
                            for i in range(n_ln)], -1) for f in ("ar", "aw")}
        sent = np.stack([ok[:, plan.chan_of_q[q]] & sel.get(("q", q), zero)
                         for q in range(n_q)], -1)
        for c, held in hold.items():
            inj_rr[:, :, c] += ok[:, c] & ~held
        left = h[..., QBEATS] - sent
        b, r, q = np.nonzero(sent)
        ring[b, r, q, head[b, r, q] % cap, QBEATS] = left[b, r, q]
        txn0 = ptr.copy()
        ptr = ptr + (inj["ar"] | inj["aw"])
        out_r = out_r + inj["ar"]
        out_w = out_w + inj["aw"]
        head = head + (sent & (left <= 0))
        started = np.where(sent, left > 0, started)

        # ---- NI: deliveries ----------------------------------------------
        def arrived(i, f):
            c = plan.ch[f][i]
            return dv[:, c] & (dflit[:, c, :, KIND] == kind(i, f)), dflit[:, c]

        def push(q, mask, entry):
            b, r = np.nonzero(mask)
            if b.size:
                ring[b, r, q, tail[b, r, q] % cap] = np.stack(
                    [np.broadcast_to(v, mask.shape)[b, r] for v in entry], -1)
                tail[b, r, q] += 1

        for i in plan.push_order_r:
            a, f = arrived(i, "ar")
            push(plan.rq_of_r[i], a, (now + sl[i], f[..., SRC], bb[i],
                                      f[..., TIME], f[..., TXN], kind(i, "r")))
        for i in range(n_ln):
            a, f = arrived(i, "w")
            push(plan.rq_of_b[i], a & (f[..., BEAT] <= 1),
                 (now + sl[i], f[..., SRC], 1, f[..., TIME], f[..., TXN],
                  kind(i, "b")))
        for i in range(n_ln):
            push(plan.n_rq + i, inj["aw"][:, :, i],
                 (now + 1, req_d[:, :, i], bb[i], now, txn0[:, :, i],
                  kind(i, "w")))

        # ---- NI: per-class statistics ------------------------------------
        for i in range(n_ln):
            a, f = arrived(i, "r")
            m["beats_rx"][:, :, i] += a
            m["first_t"][:, :, i] = np.where(a, np.minimum(
                m["first_t"][:, :, i], now), m["first_t"][:, :, i])
            m["last_t"][:, :, i] = np.where(a, now, m["last_t"][:, :, i])
            tail_beat = a & (f[..., BEAT] <= 1)
            lat = np.where(tail_beat, now - f[..., TIME], 0)
            m["done"][:, :, i] += tail_beat
            m["lat_sum"][:, :, i] += lat
            m["lat_max"][:, :, i] = np.maximum(m["lat_max"][:, :, i], lat)
            out_r[:, :, i] -= tail_beat
            a, f = arrived(i, "w")
            m["w_beats_rx"][:, :, i] += a
            m["w_first_t"][:, :, i] = np.where(a, np.minimum(
                m["w_first_t"][:, :, i], now), m["w_first_t"][:, :, i])
            m["w_last_t"][:, :, i] = np.where(a, now, m["w_last_t"][:, :, i])
            a, f = arrived(i, "b")
            lat = np.where(a, now - f[..., TIME], 0)
            m["w_done"][:, :, i] += a
            m["w_lat_sum"][:, :, i] += lat
            m["w_lat_max"][:, :, i] = np.maximum(m["w_lat_max"][:, :, i], lat)
            out_w[:, :, i] -= a

        # ---- liveness and occupancy --------------------------------------
        activity = ok.any((1, 2)) | dv.any((1, 2)) | (step_moves.sum(1) > 0)
        pending = ((out_r + out_w) > 0).any((1, 2))
        cur_stall = np.where(pending & ~activity, cur_stall + 1, 0)
        max_stall = np.maximum(max_stall, cur_stall)
        occ = count[..., :L].sum((2, 3))
        occ_sum += occ
        occ_max = np.maximum(occ_max, occ)
        moves += step_moves

    n_sched = (tt < BIG).sum(-1)
    drained = ((ptr >= n_sched).all((1, 2)) & (out_r == 0).all((1, 2))
               & (out_w == 0).all((1, 2)))
    return [_flat(cfg, plan, {k: v[b] for k, v in m.items()}, moves[b],
                  occ_sum[b], occ_max[b], max_stall[b], drained[b])
            for b in range(Bn)]


def _flat(cfg, plan, m, moves, occ_sum, occ_max, max_stall, drained):
    """The statistics a ``SimResult`` reports, derived the plain way."""
    out = {"cycles": np.asarray(cfg["cycles"])}

    def span(first, last):
        return np.maximum(last - np.minimum(first, last), 1)

    for i, c in enumerate(plan.classes):
        g = {k: v[:, i] for k, v in m.items()}              # (R,)
        pre = f"classes.{c['name']}."
        stats = {
            "done": g["done"],
            "avg_lat": g["lat_sum"] / np.maximum(g["done"], 1),
            "max_lat": g["lat_max"],
            "beats_rx": g["beats_rx"],
            "eff_bw": g["beats_rx"] / span(g["first_t"], g["last_t"]),
            "w_done": g["w_done"],
            "w_avg_lat": g["w_lat_sum"] / np.maximum(g["w_done"], 1),
            "w_max_lat": g["w_lat_max"],
            "w_beats_rx": g["w_beats_rx"],
            "w_eff_bw": g["w_beats_rx"] / span(g["w_first_t"],
                                               g["w_last_t"]),
            # one AXI ID stream per class: the per-stream view is the
            # class view with a stream axis of length one
            "stream_done": g["done"][None],
            "stream_avg_lat": (g["lat_sum"] / np.maximum(g["done"], 1))[None],
            "stream_max_lat": g["lat_max"][None],
            "stream_last_t": g["last_t"][None],
            "stream_w_done": g["w_done"][None],
            "stream_w_avg_lat": (g["w_lat_sum"]
                                 / np.maximum(g["w_done"], 1))[None],
            "stream_w_max_lat": g["w_lat_max"][None],
            "stream_w_last_t": g["w_last_t"][None],
        }
        out.update({pre + k: v for k, v in stats.items()})
    for c, ch in enumerate(cfg["channels"]):
        pre = f"channels.{ch['name']}."
        out[pre + "link_moves"] = np.asarray(moves[c])
        out[pre + "energy_pj"] = np.asarray(
            moves[c] * (ch["width_bits"] / 8.0) * cfg["pj_per_byte_hop"])
        out[pre + "vc_occupancy"] = np.asarray([occ_sum[c] / float(
            cfg["cycles"])])
        out[pre + "vc_peak_occupancy"] = np.asarray([occ_max[c]])
    out["max_stall_cycles"] = np.asarray(max_stall)
    out["drained"] = np.asarray(drained)
    return out
