"""Plain reference of one mapping job, written apart from the toolchain.

It imports nothing of the program.  Given the network, the stimulus and
the job's own partition and placement, it recomputes every answer the job
reports, layer by layer:

  profile    the LIF recurrence step by step (event-driven: only the
             synapses of neurons that fired are read), the transmission
             trace, and its cut at the Table 1 count
  partition  capacity and range of every part, and the objective the
             partitioner minimises (spikes on cut synapses, or the
             connectivity-1 multicast volume) recounted from the trace
  map        injectivity of the placement, and the average hop of the
             job's traffic model recounted from the trace (under
             multicast from the fire counts and the synapses: every
             firing of a neuron reaches the same parts)
  evaluate   a cycle-by-cycle replay of the trace through the XY mesh:
             every packet steps one link per cycle; each link grants its
             ``link_capacity`` oldest requests; under multicast one flit
             per firing forks along the XY tree, a child link requestable
             the cycle after its parent's grant, and the check replays
             the trace from its firings, building each neuron's tree once
  faults     cores and links that die at given steps: the trace replays
             in segments between the events (`fault_segments`), each under
             the failures in force on the mapping the job used there
             (`replay_faulty`, unicast), and the segments' statistics
             combine by their definitions (`combine`)

Every sum of synaptic weights is exact (weights lie on a 2^-20 grid), so
the raster is one raster, whatever order a backend adds in.
"""
from __future__ import annotations

import numpy as np

from network import Network

# ------------------------------------------------------------------ profile


def profile(net: Network, drive: np.ndarray, lif: dict) -> dict:
    """Raster statistics and trace of the kept steps.

    Returns ``num_steps`` (kept), ``fire_counts`` (N,) over the kept steps,
    ``firings`` as sorted keys ``t * N + src`` and ``trace`` as sorted
    packed keys ``(t * N + src) * N + dst``.
    """
    n = net.num_neurons
    xadj = net.xadj
    decay = np.float32(lif["decay"])
    threshold = np.float32(lif["threshold"])
    v_reset = np.float32(lif["v_reset"])
    refractory = np.int32(lif["refractory"])
    v = np.zeros(n, dtype=np.float32)
    refr = np.zeros(n, dtype=np.int32)
    fired_prev = np.empty(0, dtype=np.int64)
    target = net.target_spikes
    steps: list[np.ndarray] = []  # fired neuron ids per step
    cum = 0
    reached = None  # first step at which the count reaches the target
    for t in range(drive.shape[0]):
        syn = _synapses_of(fired_prev, xadj)
        current = np.bincount(net.syn_dst[syn], weights=net.syn_w[syn],
                              minlength=n).astype(np.float32) + drive[t]
        active = refr <= 0
        v = np.where(active, decay * v + current, v)
        fired = active & (v >= threshold)
        v = np.where(fired, v_reset, v)
        refr = np.where(fired, refractory, np.maximum(refr - 1, 0)).astype(np.int32)
        fired_prev = np.flatnonzero(fired)
        steps.append(fired_prev)
        cum += int((xadj[fired_prev + 1] - xadj[fired_prev]).sum())
        if target is not None and reached is None and cum >= target:
            reached = t
        if target is not None and cum > target:
            break  # the trace is cut at `reached`; later steps are dropped
    kept = steps[:reached + 1] if (target is not None and cum > target) else steps
    fire_counts = np.zeros(n, dtype=np.int64)
    keys, firings = [], []
    for t, ids in enumerate(kept):
        fire_counts[ids] += 1
        firings.append(t * np.int64(n) + ids)
        syn = _synapses_of(ids, xadj)
        keys.append((t * np.int64(n) + net.syn_src[syn]) * n + net.syn_dst[syn])
    trace = (np.sort(np.concatenate(keys), kind="stable") if keys
             else np.empty(0, np.int64))
    firings = (np.concatenate(firings) if firings
               else np.empty(0, np.int64))
    return {"num_steps": len(kept), "fire_counts": fire_counts,
            "firings": firings, "trace": trace}


def _synapses_of(neurons: np.ndarray, xadj: np.ndarray) -> np.ndarray:
    """Indices of the outgoing synapses of ``neurons`` (CSR gather)."""
    starts = xadj[neurons]
    lens = xadj[neurons + 1] - starts
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]])
    return np.repeat(starts - offs, lens) + np.arange(total)


def trace_keys(trace_t, trace_src, trace_dst, n: int) -> np.ndarray:
    """A program's trace in the reference's packed, sorted form."""
    keys = np.asarray(trace_t, dtype=np.int64) * n
    keys += np.asarray(trace_src)
    keys *= n
    keys += np.asarray(trace_dst)
    return np.sort(keys, kind="stable")


def unpack(keys: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    return keys // (n * n), (keys // n) % n, keys % n


def set_mismatch(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the symmetric difference of two sorted duplicate-free arrays."""
    if np.array_equal(a, b):
        return 0
    return int(a.shape[0] + b.shape[0]
               - 2 * np.intersect1d(a, b, assume_unique=True).shape[0])


# ---------------------------------------------------------------- partition


def partition_violations(part: np.ndarray, k: int, capacity: int,
                         num_cores: int) -> int:
    """Neurons outside [0, k), parts over capacity, and k over the cores."""
    part = np.asarray(part, dtype=np.int64)
    bad = int(((part < 0) | (part >= k)).sum())
    loads = np.bincount(part[(part >= 0) & (part < k)], minlength=k)
    return bad + int((loads > capacity).sum()) + int(k > num_cores)


def cut_spikes(net: Network, fire_counts: np.ndarray, part: np.ndarray) -> int:
    """Spikes carried on synapses whose ends lie in different parts."""
    cut = part[net.syn_src] != part[net.syn_dst]
    return int(fire_counts[net.syn_src[cut]].sum())


def multicast_volume(net: Network, fire_counts: np.ndarray,
                     part: np.ndarray) -> int:
    """Sum over firing neurons of fires x (distinct parts reached beyond
    the neuron's own)."""
    part = np.asarray(part, dtype=np.int64)
    k = int(part.max()) + 1
    pairs = np.unique(net.syn_src * k + part[net.syn_dst])
    src, dest_part = pairs // k, pairs % k
    remote = dest_part != part[src]
    return int(fire_counts[src[remote]].sum())


# --------------------------------------------------------------------- map


def placement_violations(placement: np.ndarray, num_cores: int) -> int:
    placement = np.asarray(placement, dtype=np.int64)
    out = int(((placement < 0) | (placement >= num_cores)).sum())
    return out + int(placement.shape[0] - np.unique(placement).shape[0])


def packets(keys: np.ndarray, n: int, part: np.ndarray,
            cast: str) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """The job's packets by its traffic model, as (t, src neuron, dest part)
    remote packets and the count of part-local deliveries.

    Unicast: one packet per transmission.  Multicast: one packet per
    distinct (firing, destination part), a firing being (t, src neuron).
    """
    t, src, dst = unpack(keys, n)
    ps, pd = part[src], part[dst]
    local = ps == pd
    t, src, pd = t[~local], src[~local], pd[~local]
    if cast == "multicast":
        k = int(part.max()) + 1
        u = np.unique((t * n + src) * k + pd)
        t, src, pd = u // (n * k), (u // k) % n, u % k
    return t, src, pd, int(local.sum())


def avg_hop(keys: np.ndarray, n: int, part: np.ndarray, placement: np.ndarray,
            mesh_w: int, cast: str) -> float:
    """Hop-weighted packets over all packets, part-local ones at 0 hops."""
    _, src, pd, n_local = packets(keys, n, part, cast)
    a, b = placement[part[src]], placement[pd]
    hops = np.abs(a % mesh_w - b % mesh_w) + np.abs(a // mesh_w - b // mesh_w)
    return int(hops.sum()) / max(int(src.shape[0]) + n_local, 1)


# ---------------------------------------------------------------- evaluate


def link_id(tail: np.ndarray, head: np.ndarray, w: int, h: int) -> np.ndarray:
    """Directed mesh link ids: east links first (by row, then column), then
    west, south (by column, then row) and north."""
    tx, ty, hx, hy = tail % w, tail // w, head % w, head // w
    east = (w - 1) * h
    south = 2 * (w - 1) * h
    north = south + w * (h - 1)
    return np.select(
        [hx == tx + 1, hx == tx - 1, hy == ty + 1],
        [ty * (w - 1) + tx, east + ty * (w - 1) + hx, south + tx * (h - 1) + ty],
        north + tx * (h - 1) + hy)


def _xy_next(cur: np.ndarray, dst: np.ndarray, w: int) -> np.ndarray:
    """The next core on the XY route: x first, then y."""
    cx, dx = cur % w, dst % w
    step_x = np.sign(dx - cx)
    step_y = np.sign(dst // w - cur // w) * w
    return cur + np.where(step_x != 0, step_x, step_y)


def _yx_next(cur: np.ndarray, dst: np.ndarray, w: int) -> np.ndarray:
    """The next core on the YX route: y first, then x."""
    step_y = np.sign(dst // w - cur // w) * w
    step_x = np.sign(dst % w - cur % w)
    return cur + np.where(step_y != 0, step_y, step_x)


def _inject_cycles(t: np.ndarray, src_core: np.ndarray, ncores: int,
                   inject_capacity: int) -> np.ndarray:
    """The r-th injection from a core in a step enters at r // capacity;
    entries are ranked in the order given."""
    key = t * ncores + src_core
    order = np.argsort(key, kind="stable")
    sk = key[order]
    first = np.concatenate([[True], sk[1:] != sk[:-1]])
    start = np.maximum.accumulate(np.where(first, np.arange(sk.shape[0]), 0))
    rank = np.empty(sk.shape[0], dtype=np.int64)
    rank[order] = np.arange(sk.shape[0]) - start
    return rank // inject_capacity


def _grants(tag: np.ndarray, capacity: int) -> np.ndarray:
    """True for the first ``capacity`` requests of each tag, requests being
    given in priority order."""
    order = np.argsort(tag, kind="stable")
    st = tag[order]
    first = np.concatenate([[True], st[1:] != st[:-1]])
    start = np.maximum.accumulate(np.where(first, np.arange(st.shape[0]), 0))
    go = np.empty(st.shape[0], dtype=bool)
    go[order] = (np.arange(st.shape[0]) - start) < capacity
    return go


def multicast_avg_hop(net: Network, fire_counts: np.ndarray, part: np.ndarray,
                      placement: np.ndarray, mesh_w: int) -> float:
    """`avg_hop` under multicast of the trace the fire counts send over the
    network's synapses: every firing of a neuron sends one packet to each
    distinct remote part its synapses reach."""
    part = np.asarray(part, dtype=np.int64)
    k = int(part.max()) + 1
    local = part[net.syn_src] == part[net.syn_dst]
    pairs = np.unique(net.syn_src[~local] * k + part[net.syn_dst[~local]])
    src, pd = pairs // k, pairs % k
    a, b = placement[part[src]], placement[pd]
    hops = np.abs(a % mesh_w - b % mesh_w) + np.abs(a // mesh_w - b // mesh_w)
    n_local = int(fire_counts[net.syn_src[local]].sum())
    packets = int(fire_counts[src].sum())
    return int((fire_counts[src] * hops).sum()) / max(packets + n_local, 1)


def replay(keys: np.ndarray, n: int, part: np.ndarray, placement: np.ndarray,
           w: int, h: int, noc: dict, cast: str) -> dict:
    """Every statistic of the queued NoC replay of the trace."""
    core = np.asarray(placement, dtype=np.int64)[np.asarray(part, np.int64)]
    t, src, dst = unpack(keys, n)
    sc, dc = core[src], core[dst]
    local = sc == dc
    n_local = int(local.sum())
    t, src, sc, dc = t[~local], src[~local], sc[~local], dc[~local]
    if cast == "multicast":
        # Each firing (t, src neuron) forks along a tree of its own, to
        # each distinct destination core once; firings in (t, src) order.
        ncores = w * h
        u = np.unique((t * n + src) * ncores + dc)
        fids, starts = np.unique(u // ncores, return_index=True)
        stats = _replay_multicast(fids // n, np.arange(fids.shape[0]),
                                  core[fids % n], np.append(starts, u.shape[0]),
                                  u % ncores, w, h, noc)
    elif cast == "unicast":
        stats = _replay_unicast(t, sc, dc, w, h, noc)
    else:
        raise ValueError(f"unknown cast {cast!r}")
    return _finish(stats, n_local, noc, cast)


def replay_firings(net: Network, firings: np.ndarray, part: np.ndarray,
                   placement: np.ndarray, w: int, h: int, noc: dict) -> dict:
    """`replay` under multicast of the trace that ``firings`` (sorted
    ``t * N + src``, as `profile` gives them) send over the network's
    synapses, from the firings: every firing of a neuron reaches the same
    cores, so each neuron's tree is built once."""
    n, ncores = net.num_neurons, w * h
    core = np.asarray(placement, dtype=np.int64)[np.asarray(part, np.int64)]
    sc, dc = core[net.syn_src], core[net.syn_dst]
    local = sc == dc
    # The distinct remote destination cores of each neuron, by neuron.
    pairs = np.unique(net.syn_src[~local] * ncores + dc[~local])
    ptr = np.concatenate([[0], np.cumsum(np.bincount(pairs // ncores,
                                                     minlength=n))])
    f_src = firings % n
    n_local = int(np.bincount(net.syn_src[local], minlength=n)[f_src].sum())
    # A firing with no remote packet injects nothing.
    sends = ptr[f_src + 1] > ptr[f_src]
    stats = _replay_multicast(firings[sends] // n, f_src[sends], core, ptr,
                              pairs % ncores, w, h, noc)
    return _finish(stats, n_local, noc, "multicast")


def _finish(stats: dict, n_local: int, noc: dict, cast: str) -> dict:
    """The statistics of a replay from its packets' latencies and hops and
    its per-link traversals."""
    e = noc["energy_pj"]
    traversals = int(stats["per_link_hops"].sum())
    lat = stats.pop("latency")
    pkt_t = stats.pop("packet_t")
    n_noc = int(lat.shape[0])
    hops = stats.pop("hops")
    total_hops = int(hops.sum())
    window_max = np.zeros(int(pkt_t.max()) + 1 if n_noc else 0, dtype=np.int64)
    np.maximum.at(window_max, pkt_t, lat)
    stats.update(
        avg_latency=float(lat.mean()) if n_noc else 0.0,
        max_latency=int(lat.max()) if n_noc else 0,
        avg_hop=float(total_hops / max(n_noc, 1)),
        total_hops=total_hops,
        edge_variance=float(np.var(stats["per_link_hops"])),
        dynamic_energy_pj=(float(traversals) * (e["router"] + e["link"])
                           + float(n_local) * e["local"]),
        num_noc_spikes=n_noc,
        num_local_spikes=n_local,
        cycles_simulated=int(window_max.sum()),
        cast=cast,
        link_traversals=traversals,
        spikes_dropped=0,
        detour_hops=0,
    )
    return stats


def _replay_unicast(t, sc, dc, w, h, noc, yx=None) -> dict:
    """One packet per transmission, stepped until every packet arrives;
    packets flagged in ``yx`` route Y first, the others X first."""
    cap = int(noc["link_capacity"])
    nl = 2 * (w - 1) * h + 2 * w * (h - 1)
    if yx is None:
        yx = np.zeros(t.shape[0], dtype=bool)
    # Record order within a step: by source core, then destination core.
    order = np.lexsort((dc, sc, t))
    t, sc, dc, yx = t[order], sc[order], dc[order], yx[order]
    inject = _inject_cycles(t, sc, w * h, int(noc["inject_capacity"]))
    hops = np.abs(sc % w - dc % w) + np.abs(sc // w - dc // w)
    # Arbitration priority: earlier injection first, then record order.
    prio = np.argsort(inject, kind="stable")
    cur = sc.copy()
    lat = np.zeros(t.shape[0], dtype=np.int64)
    per_link = np.zeros(nl, dtype=np.int64)
    congestion = 0
    alive = prio  # packets in flight, in priority order
    cycle = 0
    while alive.shape[0]:
        req = alive[inject[alive] <= cycle]
        if req.shape[0]:
            nxt = np.where(yx[req], _yx_next(cur[req], dc[req], w),
                           _xy_next(cur[req], dc[req], w))
            link = link_id(cur[req], nxt, w, h)
            go = _grants(t[req] * nl + link, cap)
            congestion += int(req.shape[0] - go.sum())
            moved = req[go]
            per_link += np.bincount(link[go], minlength=nl)
            cur[moved] = nxt[go]
            lat[moved[cur[moved] == dc[moved]]] = cycle + 1
            alive = alive[cur[alive] != dc[alive]]
        cycle += 1
    return {"latency": lat, "packet_t": t, "hops": hops,
            "per_link_hops": per_link, "congestion_count": congestion}


def _replay_multicast(f_t, f_tree, tree_core, tree_ptr, tree_dest, w, h,
                      noc) -> dict:
    """One flit per firing, forking along the XY tree of its destinations.
    Firing ``f``, in priority order after its injection cycle, is made at
    step ``f_t[f]`` and sends along tree ``s = f_tree[f]``: from core
    ``tree_core[s]`` to each of ``tree_dest[tree_ptr[s]:tree_ptr[s + 1]]``
    (distinct, none of them its own)."""
    cap = int(noc["link_capacity"])
    ncores = w * h
    nl = 2 * (w - 1) * h + 2 * w * (h - 1)
    # Tree links: the union of the XY routes of a tree's packets, one per
    # (tree, tail core, head core), in that order.
    size = np.diff(tree_ptr)
    p_tree = np.repeat(np.arange(size.shape[0]), size)
    psrc, pdst = tree_core[p_tree], tree_dest
    hops = np.abs(psrc % w - pdst % w) + np.abs(psrc // w - pdst // w)
    hop_pkt = np.repeat(np.arange(pdst.shape[0]), hops)
    step = np.arange(hop_pkt.shape[0]) - np.repeat(
        np.concatenate([[0], np.cumsum(hops)[:-1]]), hops)
    tail, head = _route_hop(psrc[hop_pkt], pdst[hop_pkt], step, w)
    links = np.unique((p_tree[hop_pkt] * ncores + tail) * ncores + head)
    l_tree = links // (ncores * ncores)
    l_tail = (links // ncores) % ncores
    l_link = link_id(l_tail, links % ncores, w, h)
    l_ptr = np.searchsorted(l_tree, np.arange(size.shape[0] + 1))
    # An XY tree enters a core at most once: (tree, head) names a link.
    enter = l_tree * ncores + links % ncores
    eorder = np.argsort(enter)
    enter_sorted = enter[eorder]
    q = l_tree * ncores + l_tail
    pos = np.minimum(np.searchsorted(enter_sorted, q), links.shape[0] - 1)
    l_parent = np.where((enter_sorted[pos] == q)
                        & (l_tail != tree_core[l_tree]), eorder[pos], -1)
    p_into = eorder[np.searchsorted(enter_sorted, p_tree * ncores + pdst)]
    # Each firing runs the links of its tree: entity ``off[f] + l`` is
    # firing f's flit on tree link l.
    f_lo, f_hi = l_ptr[f_tree], l_ptr[f_tree + 1]
    base = np.concatenate([[0], np.cumsum(f_hi - f_lo)])
    off = base[:-1] - f_lo
    e_f = np.repeat(np.arange(f_t.shape[0]), f_hi - f_lo)
    e_l = _ranges(f_lo, f_hi)
    ne = e_l.shape[0]
    parent = np.where(l_parent[e_l] >= 0, off[e_f] + l_parent[e_l], -1)
    kids = np.argsort(parent, kind="stable")
    kid_ptr = np.concatenate([[0], np.cumsum(np.bincount(parent + 1,
                                                         minlength=ne + 1))])
    inject = _inject_cycles(f_t, tree_core[f_tree], ncores,
                            int(noc["inject_capacity"]))
    # Arbitration priority: earlier injection first, then firing order.
    order = np.argsort(inject, kind="stable")
    by_prio = _ranges(base[order], base[order + 1])
    prio = np.empty(ne, dtype=np.int64)
    prio[by_prio] = np.arange(ne)
    # A request's key orders by its tag (step, link), then by priority:
    # the tag in the high bits, the priority in the low ``shift``.
    shift = max(ne - 1, 1).bit_length()
    key = ((f_t[e_f] * nl + l_link[e_l]) << shift) | prio
    # Roots request from their injection cycle, children from the cycle
    # after their parent's grant; a request waits until it is granted.
    roots = np.flatnonzero(parent < 0)
    roots = roots[np.argsort(inject[e_f[roots]], kind="stable")]
    root_at = inject[e_f[roots]]
    grant = np.full(ne, -1, dtype=np.int64)
    congestion = 0
    waiting = born = np.empty(0, dtype=np.int64)  # sorted keys; entities
    cycle = next_root = 0
    while True:
        hi = int(np.searchsorted(root_at, cycle, "right"))
        new = np.sort(key[np.concatenate([roots[next_root:hi], born])])
        next_root = hi
        if not (waiting.shape[0] or new.shape[0]):
            if next_root == roots.shape[0]:
                break
            cycle = int(root_at[next_root])  # nothing requests before
            continue
        req = np.insert(waiting, np.searchsorted(waiting, new), new)
        # The first ``cap`` requests of each tag are granted.
        tag = req >> shift
        go = np.ones(req.shape[0], dtype=bool)
        go[cap:] = tag[cap:] != tag[:-cap]
        won, waiting = by_prio[req[go] & ((1 << shift) - 1)], req[~go]
        congestion += int(waiting.shape[0])
        grant[won] = cycle
        born = kids[_ranges(kid_ptr[won + 1], kid_ptr[won + 2])]
        cycle += 1
    # A packet arrives the cycle after the link into its core is granted.
    pk_f = np.repeat(np.arange(f_t.shape[0]), size[f_tree])
    pk = _ranges(tree_ptr[f_tree], tree_ptr[f_tree + 1])
    return {"latency": grant[off[pk_f] + p_into[pk]] + 1,
            "packet_t": f_t[pk_f], "hops": hops[pk],
            "per_link_hops": np.bincount(l_link[e_l], minlength=nl),
            "congestion_count": congestion}


def _route_hop(src, dst, step, w):
    """(tail, head) core of hop ``step`` of the XY route src -> dst."""
    sx, sy, dx, dy = src % w, src // w, dst % w, dst // w
    hx = np.abs(dx - sx)
    sgx, sgy = np.sign(dx - sx), np.sign(dy - sy)
    horizontal = step < hx
    tx = np.where(horizontal, sx + sgx * step, dx)
    ty = np.where(horizontal, sy, sy + sgy * (step - hx))
    hxn = np.where(horizontal, tx + sgx, tx)
    hyn = np.where(horizontal, ty, ty + sgy)
    return ty * w + tx, hyn * w + hxn


def _ranges(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Concatenation of arange(lo[i], hi[i])."""
    lens = hi - lo
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]])
    return np.repeat(lo - offs, lens) + np.arange(total)


# ------------------------------------------------------------------ faults


def link_of(tail: int, head: int, w: int, h: int) -> int:
    """The id of the mesh link from core ``tail`` to its neighbour ``head``."""
    tx, ty, hx, hy = tail % w, tail // w, head % w, head // w
    if not (0 <= tail < w * h and 0 <= head < w * h
            and abs(tx - hx) + abs(ty - hy) == 1):
        raise ValueError(f"no mesh link from core {tail} to core {head}")
    return int(link_id(np.int64(tail), np.int64(head), w, h))


def fault_state(events: list[dict], t: int, w: int,
                h: int) -> tuple[np.ndarray, np.ndarray]:
    """(dead cores, blocked links) once every event at or before step ``t``
    has happened.  An event kills cores (``kind`` "core", ``ids``) or one
    link (``kind`` "link", named by its ``from`` and ``to`` cores).  A
    blocked link is a dead one or one whose tail or head router is dead."""
    dead = np.zeros(w * h, dtype=bool)
    blocked = np.zeros(2 * (w - 1) * h + 2 * w * (h - 1), dtype=bool)
    for ev in events:
        if int(ev["t"]) > t:
            continue
        if ev["kind"] == "core":
            dead[list(ev["ids"])] = True
        elif ev["kind"] == "link":
            blocked[link_of(int(ev["from"]), int(ev["to"]), w, h)] = True
        else:
            raise ValueError(f"unknown fault kind {ev['kind']!r}")
    cores = np.arange(w * h)
    x, y = cores % w, cores // w
    for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
        ok = (x + dx >= 0) & (x + dx < w) & (y + dy >= 0) & (y + dy < h)
        tail = cores[ok]
        head = tail + dx + dy * w
        blocked[link_id(tail, head, w, h)[dead[tail] | dead[head]]] = True
    return dead, blocked


def fault_segments(events: list[dict], detect_windows: int, t_end: int,
                   w: int, h: int) -> list[dict]:
    """The segments ``[lo, hi)`` of steps that a faulted replay runs, in
    order.  The replay runs up to each event step.  After a step that kills
    a core, the next ``detect_windows`` steps (up to the next event at
    most) replay on the stale mapping under the new failures (``lag``);
    then the mapping is repaired, and ``repaired`` marks the first segment
    on the new one.  A step that kills only links changes the failures
    alone.  Events at or after ``t_end`` change nothing.  Each segment
    carries the failures in force (``dead``, ``blocked``) and the cores
    dead at the last repair before it (``avoid``; None before any)."""
    times = sorted({int(e["t"]) for e in events})
    dead, blocked = fault_state([], 0, w, h)
    segments: list[dict] = []
    cursor, avoid, repaired = 0, None, False

    def add(lo: int, hi: int, lag: bool) -> None:
        segments.append({"lo": lo, "hi": hi, "lag": lag, "repaired": repaired,
                         "dead": dead, "blocked": blocked, "avoid": avoid})

    for i, te in enumerate(times):
        if te >= t_end:
            break
        add(cursor, te, False)
        repaired = False
        cursor = max(cursor, te)
        dead, blocked = fault_state(events, te, w, h)
        if not any(e["kind"] == "core" for e in events if int(e["t"]) == te):
            continue
        end = min([cursor + max(detect_windows, 0), t_end] + times[i + 1:i + 2])
        add(cursor, end, True)
        cursor, avoid, repaired = end, dead, True
    add(cursor, t_end, False)
    return segments


def replayed(segments: list[dict], keys: np.ndarray, n: int) -> list[dict]:
    """The segments that hold records of the trace (sorted packed keys),
    each with its ``keys``, ``records`` and first and last step; a segment
    with none is not replayed, and passes its ``repaired`` mark on."""
    out, repaired = [], False
    per_step = np.int64(n) * n
    for seg in segments:
        lo, hi = np.searchsorted(keys, [seg["lo"] * per_step,
                                        seg["hi"] * per_step])
        repaired |= seg["repaired"]
        if hi <= lo:
            continue
        seg_keys = keys[lo:hi]
        out.append({**seg, "repaired": repaired, "keys": seg_keys,
                    "records": int(hi - lo),
                    "t_first": int(seg_keys[0] // per_step),
                    "t_last": int(seg_keys[-1] // per_step)})
        repaired = False
    return out


def replay_faulty(keys: np.ndarray, n: int, part: np.ndarray,
                  placement: np.ndarray, w: int, h: int, noc: dict,
                  dead: np.ndarray, blocked: np.ndarray) -> dict:
    """Every statistic of the queued unicast replay under failures.  A
    core-local delivery on a dead core is dropped; so is a remote packet
    with a dead endpoint.  A packet whose XY route crosses no blocked link
    goes XY; otherwise it goes YX if that route is clean, and its route
    hops count as ``detour_hops``; otherwise it is dropped."""
    core = np.asarray(placement, dtype=np.int64)[np.asarray(part, np.int64)]
    t, src, dst = unpack(keys, n)
    sc, dc = core[src], core[dst]
    local = sc == dc
    dropped = int((local & dead[sc]).sum())
    n_local = int(local.sum()) - dropped
    t, sc, dc = t[~local], sc[~local], dc[~local]
    xy_bad = _route_blocked(sc, dc, blocked, w, h, _xy_next)
    yx_bad = _route_blocked(sc, dc, blocked, w, h, _yx_next)
    go = ~(dead[sc] | dead[dc]) & ~(xy_bad & yx_bad)
    yx = go & xy_bad
    stats = _finish(_replay_unicast(t[go], sc[go], dc[go], w, h, noc, yx[go]),
                    n_local, noc, "unicast")
    hops = np.abs(sc % w - dc % w) + np.abs(sc // w - dc // w)
    stats.update(spikes_dropped=dropped + int((~go).sum()),
                 detour_hops=int(hops[yx].sum()))
    return stats


def _route_blocked(src, dst, blocked, w, h, step) -> np.ndarray:
    """True where the route that ``step`` walks from src to dst crosses a
    blocked link."""
    cur = src.copy()
    hit = np.zeros(src.shape[0], dtype=bool)
    idx = np.flatnonzero(cur != dst)
    while idx.shape[0]:
        nxt = step(cur[idx], dst[idx], w)
        hit[idx] |= blocked[link_id(cur[idx], nxt, w, h)]
        cur[idx] = nxt
        idx = idx[nxt != dst[idx]]
    return hit


_SUMMED = ("total_hops", "congestion_count", "dynamic_energy_pj",
           "num_noc_spikes", "num_local_spikes", "cycles_simulated",
           "link_traversals", "spikes_dropped", "detour_hops")


def combine(parts: list[dict]) -> dict:
    """The statistics of a replay made in segments, from the segments'
    statistics by their definitions: counts, energy, hops, cycles and the
    per-link histogram sum; the average latency re-weights by NoC packets;
    the maximum is the largest; the edge variance is the summed
    histogram's.  A single segment is its own record."""
    if len(parts) == 1:
        return parts[0]
    out = dict(parts[0])
    for key in _SUMMED:
        out[key] = sum(p[key] for p in parts)
    n_noc = out["num_noc_spikes"]
    per_link = np.sum([p["per_link_hops"] for p in parts], axis=0)
    out.update(
        avg_latency=(sum(p["avg_latency"] * p["num_noc_spikes"] for p in parts)
                     / n_noc if n_noc else 0.0),
        max_latency=max(p["max_latency"] for p in parts),
        avg_hop=out["total_hops"] / n_noc if n_noc else 0.0,
        per_link_hops=per_link,
        edge_variance=float(np.var(per_link)),
    )
    return out


def remap_violations(part: np.ndarray, placement: np.ndarray, capacity: int,
                     num_cores: int, dead: np.ndarray) -> int:
    """Of a repaired mapping: neurons outside the placement's parts or on a
    dead core, parts over capacity, placements off the mesh or sharing a
    core, and parts with neurons placed on a dead core."""
    part = np.asarray(part, dtype=np.int64)
    placement = np.asarray(placement, dtype=np.int64)
    k = placement.shape[0]
    inside = (part >= 0) & (part < k)
    loads = np.bincount(part[inside], minlength=k)
    bad = int((~inside).sum()) + int((loads > capacity).sum())
    bad += placement_violations(placement, num_cores)
    on_mesh = (placement >= 0) & (placement < num_cores)
    on_dead = on_mesh & dead[np.clip(placement, 0, num_cores - 1)]
    bad += int(on_dead[part[inside]].sum()) + int((on_dead & (loads > 0)).sum())
    return bad
