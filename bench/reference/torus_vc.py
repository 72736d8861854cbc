"""Plain reference of the FlooNoC cycle model on XY meshes and tori
with virtual channels, in numpy.

It simulates what a configuration file states, as
``bench/reference/mesh_axi.py`` does (whose network interfaces, flit
and queue layouts it keeps: read that file's docstring for the NI
semantics), on a fabric that may wrap round and whose links may carry
``n_vcs`` virtual channels.  It imports nothing of the program under
test and reads nothing the program made.

The fabric follows the dateline scheme of Dally & Towles, *Principles
and Practices of Interconnection Networks* (2004), ch. 14:

* Routes are dimension-ordered, X first.  On a torus each dimension
  takes the shorter way round its ring; a tie (half the ring) goes the
  positive way (E, S).
* The hop that leaves a router rides VC 0 while the current ring's wrap
  link still lies beyond the next router, and VC 1 (the escape VC) once
  the flit has crossed it, or where it never needs it.  A wrap link
  therefore always delivers into VC 1.  Delivery takes the Local port,
  which has no VC.
* Each of the four directions ``p`` is ``n_vcs`` virtual ports
  ``p * n_vcs + v``, each with its own input FIFO, output register,
  round-robin pointer and wormhole lock; round-robin arbitration runs
  over all ``4 * n_vcs + 1`` virtual inputs.  Output ``p * n_vcs + v``
  feeds the neighbour's input ``opposite(p) * n_vcs + v``.
* A physical link moves at most one flit per cycle: of the output
  registers of one link that can drain, only the highest VC's does.
* ``link_moves`` counts physical link traversals; ``vc_occupancy`` and
  ``vc_peak_occupancy`` are per VC: the input-FIFO occupancy summed
  over routers and directions at the end of each cycle, divided by the
  horizon (mean) or maximised (peak).

Departures from the cited description:

* The book gives every virtual channel of a link an equal share of its
  cycles; here the highest ready VC always wins the link (escape-VC
  priority).
* A mesh has no rings: every hop rides VC 0, whatever ``n_vcs`` is.
* VCs above 1 carry nothing: the scheme needs two.
* At ``n_vcs = 1`` every hop rides the one VC, so a torus may deadlock
  (the run then ends undrained); on a mesh this is the model of
  ``mesh_axi``, result for result.

``depth_offset`` breaks the link-level back-pressure guarantee, as in
``mesh_axi``: the control that the comparison has to reject.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np


def _sibling(name: str):
    """A reference module beside this file, loaded by its path."""
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"torus_vc_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


mesh_axi = _sibling("mesh_axi")
Plan, kind, BIG, NO_PORT = (mesh_axi.Plan, mesh_axi.kind, mesh_axi.BIG,
                            mesh_axi.NO_PORT)
N, E, S, W = mesh_axi.N, mesh_axi.E, mesh_axi.S, mesh_axi.W   # directions
DEST, SRC, TIME, KIND, TXN, BEAT = range(6)          # flit fields
READY, QDEST, QBEATS, QTIME, QTXN, QKIND = range(6)  # queue-entry fields
STEP = {N: (0, -1), E: (1, 0), S: (0, 1), W: (-1, 0)}
BACK = {N: S, E: W, S: N, W: E}


def fabric(nx: int, ny: int, topo_kind: str, V: int):
    """(nbr, opp, route, feed_r, feed_o) of an ``nx x ny`` mesh or torus
    in virtual-port space: ``nbr[r, q]``/``opp[r, q]`` the router and
    input port output ``q`` feeds, ``route[r, d]`` the virtual output
    port towards destination ``d``, ``feed_r``/``feed_o`` the router and
    output port feeding each input (-1: none)."""
    torus = topo_kind == "torus"
    R, Pv = nx * ny, 4 * V + 1
    Lv = Pv - 1
    x, y = np.arange(R) % nx, np.arange(R) // nx
    nbr = np.full((R, Pv), -1)
    opp = np.full((R, Pv), Lv)
    feed_r = np.full((R, Pv), -1)
    feed_o = np.full((R, Pv), -1)
    for p, (dx, dy) in STEP.items():
        tx, ty = x + dx, y + dy
        if torus:               # a ring of one router has no links
            on = np.full(R, (nx > 1) if dx else (ny > 1))
            tx, ty = tx % nx, ty % ny
        else:
            on = (0 <= tx) & (tx < nx) & (0 <= ty) & (ty < ny)
        t = (ty * nx + tx)[on]
        for v in range(V):
            q, q_in = p * V + v, BACK[p] * V + v
            nbr[on, q], opp[on, q] = t, q_in
            feed_r[t, q_in], feed_o[t, q_in] = np.flatnonzero(on), q

    def delta(a, b, n):         # signed hops a -> b along one axis
        d = b - a
        if torus:
            d = d % n
            d = np.where(d <= n - d, d, d - n)
        return d

    cx, cy = x[:, None], y[:, None]                 # (router, destination)
    tx, ty = x[None, :], y[None, :]
    ddx, ddy = delta(cx, tx, nx), delta(cy, ty, ny)
    port = np.where(ddx > 0, E, np.where(ddx < 0, W, np.where(
        ddy > 0, S, np.where(ddy < 0, N, -1))))
    vc = np.zeros_like(port)
    if torus:
        # the wrap link lies beyond the next router: going on from it
        # the same way, the flit passes the ring's end before its target
        wrap_ahead = np.select(
            [port == E, port == W, port == S, port == N],
            [(cx + 1) % nx > tx, (cx - 1) % nx < tx,
             (cy + 1) % ny > ty, (cy - 1) % ny < ty], False)
        vc = np.minimum(np.where(wrap_ahead, 0, 1), V - 1)
    route = np.where(port < 0, Lv, port * V + vc)
    return nbr, opp, route, feed_r, feed_o


def simulate(cfg: dict, times, dests, writes, depths=None,
             depth_offset: int = 0) -> list[dict]:
    """Run ``B`` experiments of one configuration side by side.

    ``times``/``dests``/``writes`` are (B, L, R, T) per class schedules
    (``times >= BIG`` marks an empty slot); ``depths`` is (B, n_ch),
    the FIFO depth of each network in each experiment (default: the
    configuration's).  Returns one flat dict per experiment, keyed as
    ``bench.compare.flatten`` keys a ``SimResult``."""
    topo = cfg["topology"]
    if (topo["kind"] not in ("mesh", "torus")
            or topo.get("routing", "xy") != "xy" or topo.get("express")):
        raise ValueError(f"the reference models XY meshes and tori "
                         f"without express links, not {topo}")
    if any(c.get("n_streams", 1) != 1 or c.get("service_jitter", 0)
           for c in cfg["classes"]):
        raise ValueError("the reference models one AXI ID stream and a "
                         "fixed service latency per class")
    nx, ny, V = topo["nx"], topo["ny"], topo.get("n_vcs", 1)
    R = nx * ny
    Pv = 4 * V + 1                  # virtual ports; Local is last
    Lv = Pv - 1
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
    nbr, opp, route, feed_r, feed_o = fabric(nx, ny, topo["kind"], V)
    has_feed = feed_r >= 0
    fr, fo = np.clip(feed_r, 0, None), np.clip(feed_o, 0, None)
    nb = np.clip(nbr, 0, None)
    is_local = np.arange(Pv) == Lv
    rows = np.arange(R)
    dep4 = depth[:, :, None, None]

    # schedules as (B, R, L, T)
    tt = times.transpose(0, 2, 1, 3)
    dd = dests.transpose(0, 2, 1, 3)
    ww = writes.transpose(0, 2, 1, 3)

    # fabric state, (B, C, R, virtual port, ...)
    fifo = np.zeros((Bn, n_ch, R, Pv, D, 6), np.int64)
    count = np.zeros((Bn, n_ch, R, Pv), np.int64)
    rr = np.zeros((Bn, n_ch, R, Pv), np.int64)
    oreg = np.zeros((Bn, n_ch, R, Pv, 6), np.int64)
    oreg_v = np.zeros((Bn, n_ch, R, Pv), bool)
    lock = np.full((Bn, n_ch, R, Pv), -1, np.int64)
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
    occ_sum = np.zeros((Bn, n_ch, V), np.int64)
    occ_max = np.zeros((Bn, n_ch, V), np.int64)
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
        # one flit per physical link: its highest ready VC drains
        e = drain[..., :Lv].reshape(Bn, n_ch, R, 4, V)
        top = V - 1 - np.argmax(e[..., ::-1], axis=-1)
        drain[..., :Lv] = (e & (np.arange(V) == top[..., None])).reshape(
            Bn, n_ch, R, Lv)
        recv_v = has_feed & drain[:, :, fr, fo]
        inj_ok = iv & (count[..., Lv] < depth[:, :, None])
        recv_v[..., Lv] = inj_ok
        step_moves = drain[..., :Lv].sum((2, 3))
        dv, dflit = drain[..., Lv], oreg[..., Lv, :].copy()
        dst_in = np.flatnonzero(recv_v)               # flat input ports
        bc_in, r_in, p_in = dst_in // (R * Pv), dst_in // Pv % R, dst_in % Pv
        feeder = (bc_in * R + fr[r_in, p_in]) * Pv + fo[r_in, p_in]
        flit_in = np.where((p_in == Lv)[:, None],
                           iflit.reshape(-1, 6)[dst_in // Pv],
                           oreg.reshape(-1, 6)[feeder])

        # ---- routers: round-robin arbitration into free output registers -
        # (flat over (point, network, router, port): only occupied input
        # FIFOs request, so the work follows the traffic)
        fl_count, fl_fifo = count.reshape(-1), fifo.reshape(-1, D, 6)
        fl_rr, fl_lock = rr.reshape(-1), lock.reshape(-1)
        free = (~oreg_v | drain).reshape(-1)
        src = np.flatnonzero(fl_count > 0)            # occupied inputs
        bcr, i_in = src // Pv, src % Pv
        hflit = fl_fifo[src, 0]
        o_out = route[bcr % R, hflit[:, DEST]]
        dst = bcr * Pv + o_out                         # requested output
        lk = fl_lock[dst]
        ok_req = free[dst] & ((lk < 0) | (lk == i_in))
        src, i_in, hflit, dst = src[ok_req], i_in[ok_req], hflit[ok_req], \
            dst[ok_req]
        prio = (i_in - fl_rr[dst]) % Pv
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
        fl_rr[dst[unlocked]] = (i_in[unlocked] + 1) % Pv
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
        occ = count[..., :Lv].reshape(Bn, n_ch, R * 4, V).sum(2)
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
    """``mesh_axi``'s statistics, with the occupancy of every VC."""
    out = mesh_axi._flat(cfg, plan, m, moves, occ_sum[:, 0], occ_max[:, 0],
                         max_stall, drained)
    for c, ch in enumerate(cfg["channels"]):
        pre = f"channels.{ch['name']}."
        out[pre + "vc_occupancy"] = occ_sum[c] / float(cfg["cycles"])
        out[pre + "vc_peak_occupancy"] = np.asarray(occ_max[c])
    return out
