"""Plain reference of the slot simulator, the yardstick that decides
`correct`.

It imports nothing of the program under test and takes none of its
tables: the lattice, its labels, neighbours and minimal routing records
are rebuilt here from the configuration's generator matrix, and the
traffic is drawn again from the seed with `jax.random` on the host CPU.
The router follows the semantics the program documents (arXiv:1311.2019
§6.2 and its own docstrings), written as a straightforward per-slot
sweep over ports in numpy:

  * one packet per link per slot; per-input-port FIFOs of Q slots;
  * DOR over minimal records, the Remark-30 coin choosing between the
    record r and -route(-v);
  * the bubble rule: entering a ring (turn or injection) needs 2 free
    slots, continuing in the same dimension 1;
  * arbitration of an output link by the smallest key
    ``prio * PQ + (q + slot) % PQ`` over the queue slots that request it;
  * acceptance swept over in-ports in index order, so in-port p sees the
    slots vacated by departures through ports p' < p;
  * transit fills the first free slot, injection (after transit) the
    last; refused demand stays as backlog;
  * counters from slot `warmup`, latency over packets born at or after
    it, a (B,) age histogram with an overflow bucket.

The virtual-channel router (V > 1, credit flow, escape lane 0 plus
adaptive lanes) and a transient link fault follow in `run_vc_lane`.

`want` decides injection from the uniform draw and the offered load.
The default compares in float32, the precision the configurations
state; `want_bf16` is the control, the same comparison rounded to
bfloat16, which the check has to refuse.
"""
from __future__ import annotations

import itertools

import ml_dtypes
import numpy as np

INT_MAX = np.iinfo(np.int32).max


def want_f32(u: np.ndarray, load: float) -> np.ndarray:
    return u.astype(np.float32) < np.float32(load)


def want_bf16(u: np.ndarray, load: float) -> np.ndarray:
    bf = ml_dtypes.bfloat16
    return u.astype(bf).astype(np.float32) < np.float32(bf(load))


class Lattice:
    """G(H) for a generator matrix H given in Hermite normal form: upper
    triangular, positive diagonal, 0 <= H[i, j] < H[i, i] for j > i.
    Nodes are the labels of the Hermite box in mixed radix (index 0 is
    the origin); port 2i steps +e_i, port 2i+1 steps -e_i."""

    def __init__(self, matrix):
        H = np.asarray(matrix, dtype=np.int64)
        n = H.shape[0]
        if H.shape != (n, n) or np.any(np.tril(H, -1)):
            raise ValueError("the reference needs an upper-triangular matrix")
        d = np.diagonal(H)
        if np.any(d <= 0) or any(not 0 <= H[i, j] < d[i]
                                 for i in range(n) for j in range(i + 1, n)):
            raise ValueError("the reference needs the Hermite normal form")
        self.H, self.n = H, n
        self.N = int(np.prod(d))
        self.P = 2 * n
        self.strides = np.array(
            [int(np.prod(d[i + 1:])) for i in range(n)], np.int64)
        self.labels = np.stack(
            np.meshgrid(*[np.arange(a) for a in d], indexing="ij"),
            axis=-1).reshape(-1, n)
        eye = np.eye(n, dtype=np.int64)
        self.nbr = np.stack(
            [self.index(self.labels + s * eye[i])
             for i in range(n) for s in (1, -1)], axis=1)
        self.rec_a = self.route(self.labels)
        self.rec_b = -self.route(-self.labels)

    def reduce(self, v: np.ndarray) -> np.ndarray:
        """v modulo the lattice, into the Hermite box."""
        v = np.array(v, dtype=np.int64)
        for i in range(self.n - 1, -1, -1):
            v -= (v[..., i] // self.H[i, i])[..., None] * self.H[:, i]
        return v

    def index(self, v: np.ndarray) -> np.ndarray:
        return (self.reduce(v) * self.strides).sum(-1)

    def route(self, v: np.ndarray) -> np.ndarray:
        """A minimal-L1 record of each coset v + H·Z^n.  Coordinates are
        fixed from the last dimension down; at each level the residual
        coordinate c in [0, H_ii) is kept or replaced by c - H_ii, and
        of all 2^n such records the shortest wins.  Ties go to the record
        that keeps c at the highest level where two choices differ, so a
        half-ring offset routes in the + direction."""
        best = bestlen = None
        for bits in itertools.product((0, 1), repeat=self.n):
            w = np.array(v, dtype=np.int64)
            for i in range(self.n - 1, -1, -1):
                w -= (w[..., i] // self.H[i, i])[..., None] * self.H[:, i]
                if bits[self.n - 1 - i]:
                    w -= self.H[:, i]
            length = np.abs(w).sum(-1)
            if best is None:
                best, bestlen = w, length
            else:
                shorter = length < bestlen
                best = np.where(shorter[..., None], w, best)
                bestlen = np.minimum(bestlen, length)
        return best


def dor_port(rec: np.ndarray, P: int) -> np.ndarray:
    """First nonzero dimension i of the record -> port 2i (+) or 2i+1 (-);
    P where the record is zero."""
    nz = rec != 0
    dim = nz.argmax(-1)
    sgn = np.take_along_axis(rec, dim[..., None], -1)[..., 0]
    return np.where(nz.any(-1), 2 * dim + (sgn < 0), P)


def hop_table(n: int) -> np.ndarray:
    hop = np.zeros((2 * n, n), np.int64)
    for i in range(n):
        hop[2 * i, i], hop[2 * i + 1, i] = 1, -1
    return hop


def _hist(age, meas, bins: int) -> np.ndarray:
    return np.bincount(np.clip(age[meas], 0, bins - 1), minlength=bins)


def run_lane(lat: Lattice, load: float, tr: dict, *, slots: int,
             warmup: int, queue: int, hist_bins: int, want=want_f32) -> dict:
    """One single-FIFO (V = 1) lane of `slots` slots on a pristine
    lattice.  `tr` holds the lane's draws: u, coin, di (slots, N) and
    prio (slots, N, P·Q).  Returns the run-end counters.  Each queued
    packet keeps its record, birth slot and the DOR port its record asks
    for next (P marks a free slot)."""
    N, n, P, Q = lat.N, lat.n, lat.P, queue
    PQ = P * Q
    nbr = lat.nbr
    ports = np.arange(P)
    sender = nbr[:, ports ^ 1]                     # src of in-port p
    hop = hop_table(n).astype(np.int16)
    rows = np.arange(N)
    rot = np.arange(PQ, dtype=np.int32)
    rec_ab = np.stack([lat.rec_a, lat.rec_b], 1).astype(np.int16)
    port_ab = dor_port(rec_ab, P)
    rec = np.zeros((N, P, Q, n), np.int16)
    birth = np.full((N, P, Q), -1, np.int32)
    nxt = np.full((N, P, Q), P, np.int8)
    backlog = np.zeros(N, np.int64)
    c = dict(delivered=0, injected=0, lat_sum=0, lat_cnt=0)
    hist = np.zeros(hist_bins, np.int64)
    for slot in range(slots):
        counted = slot >= warmup
        occ = birth >= 0
        want_port = nxt.reshape(N, PQ)
        key = (tr["prio"][slot].astype(np.int32) * PQ
               + (rot + slot) % PQ)
        # winner of each output link: the smallest key requesting it
        widx = np.zeros((N, P), np.int64)
        whas = np.zeros((N, P), bool)
        for p in range(P):
            k = np.where(want_port == p, key, INT_MAX)
            widx[:, p] = k.argmin(1)
            whas[:, p] = k[rows, widx[:, p]] < INT_MAX
        flat_rec = rec.reshape(N, PQ, n)
        flat_birth = birth.reshape(N, PQ)
        # the packet offered to in-port p of node m by its sender
        has = whas[sender, ports]
        wi = widx[sender, ports]
        in_rec = flat_rec[sender, wi]
        in_birth = flat_birth[sender, wi]
        rec_after = in_rec - hop[None]
        done = ~rec_after.any(-1)
        deliver = has & done
        need = np.where(wi // Q == ports[None], 1, 2)
        free0 = Q - occ.sum(2)
        vac = np.zeros((N, P), np.int64)
        acc = np.zeros((N, P), bool)
        for p in range(P):
            acc[:, p] = (has[:, p] & ~done[:, p]
                         & (free0[:, p] + vac[:, p] >= need[:, p]))
            leaves = whas[:, p] & (deliver[nbr[:, p], p] | acc[nbr[:, p], p])
            vac[rows, widx[:, p] // Q] += leaves
        moved = deliver | acc
        # clear the departed winners, then transit into the first free slot
        dep = whas & moved[nbr, ports[None]]
        s_idx, p_idx = np.nonzero(dep)
        w_idx = widx[s_idx, p_idx]
        birth = birth.reshape(N, PQ)
        nxt = nxt.reshape(N, PQ)
        birth[s_idx, w_idx] = -1
        nxt[s_idx, w_idx] = P
        birth = birth.reshape(N, P, Q)
        nxt = nxt.reshape(N, P, Q)
        m_idx, q_idx = np.nonzero(acc)
        first = (birth[m_idx, q_idx] < 0).argmax(1)
        moving = rec_after[m_idx, q_idx]
        rec[m_idx, q_idx, first] = moving
        birth[m_idx, q_idx, first] = in_birth[m_idx, q_idx]
        nxt[m_idx, q_idx, first] = dor_port(moving, P)
        # injection after transit, into the last free slot
        di = tr["di"][slot]
        coin = tr["coin"][slot]
        r = rec_ab[di, coin]
        ip = port_ab[di, coin]
        want_new = want(tr["u"][slot], load)
        free_ip = (birth[rows, ip] < 0).sum(1)
        can = (want_new | (backlog > 0)) & (free_ip >= 2) & (di != 0)
        s_idx = np.nonzero(can)[0]
        ips = ip[s_idx]
        last = Q - 1 - (birth[s_idx, ips, ::-1] < 0).argmax(1)
        rec[s_idx, ips, last] = r[s_idx]
        birth[s_idx, ips, last] = slot
        nxt[s_idx, ips, last] = ips
        backlog = np.clip(backlog + want_new - can, 0, 1 << 30)
        # counters
        age = slot + 1 - in_birth
        meas = deliver & (in_birth >= warmup)
        c["lat_sum"] += int(age[meas].sum())
        c["lat_cnt"] += int(meas.sum())
        if counted:
            c["delivered"] += int(deliver.sum())
            c["injected"] += int(can.sum())
        if hist_bins:
            hist += _hist(age, meas, hist_bins)
    c["in_flight"] = int((birth >= 0).sum())
    c["dropped"] = 0
    if hist_bins:
        c["latency_hist"] = hist
    return c


def vc_select(rec: np.ndarray, link_ok: np.ndarray, credit: np.ndarray,
              rot: int) -> tuple[np.ndarray, np.ndarray]:
    """(port, lane) request of each packet under the credit VC router.

    rec (M, n); link_ok (M, P) liveness of each candidate port; credit
    (M, P, V) the credits each candidate queue advertises.  Candidates
    are the productive live ports on the adaptive lanes 1..V-1 with at
    least one credit; the most credits win, equal credits rotate with
    `rot` over the flattened (port, lane) candidates.  With none, the
    packet asks for its DOR port on the escape lane 0."""
    M, n = rec.shape
    P, V = 2 * n, credit.shape[-1]
    C = P * (V - 1)
    prod = np.stack([rec > 0, rec < 0], -1).reshape(M, P)
    dor = np.where(prod.any(1), prod.argmax(1), P)
    elig = (prod & link_ok)[:, :, None] & (credit[:, :, 1:] > 0)
    score = np.where(elig.reshape(M, C),
                     credit[:, :, 1:].reshape(M, C) * C
                     + (np.arange(C) + rot) % C, -1)
    best = score.argmax(1)
    has = score[np.arange(M), best] >= 0
    port = np.where(has, best // (V - 1), dor)
    lane = np.where(has, best % (V - 1) + 1, 0)
    return port, lane


def run_vc_lane(lat: Lattice, load: float, tr: dict, *, slots: int,
                warmup: int, queue: int, vcs: int, credits: int,
                hist_bins: int, link_ok: np.ndarray, want=want_f32) -> dict:
    """One lane of the credit-flow virtual-channel router (V = `vcs`
    lanes per input port, credit-gated minimal-adaptive lanes 1..V-1 and
    a DOR escape lane 0), under the per-slot channel liveness
    `link_ok` (slots, N, P): a dead channel moves nothing and packets
    that want it wait.  No node dies, so nothing is dropped.  The
    harness builds `link_ok` from the configuration's faults: all live,
    a `link_flap`, or one of a `link_sets` configuration's K sets of
    link events (`link_events_mask`), which the `simulate_scenario_sweep`
    and `simulate_schedule_sweep` entries run under the same traffic.

    Each packet re-chooses its (out-port, lane) every slot against the
    credits its candidate downstream queues advertise.  Acceptance needs
    one credit to continue in the same (port, lane) or on an adaptive
    lane, two to enter the escape lane of a ring; injection needs two
    local credits.  Returns the run-end counters, the per-lane counters
    and the per-slot timeline."""
    N, n, P, Q, V = lat.N, lat.n, lat.P, queue, vcs
    PV, PVQ = P * V, P * V * Q
    nbr = lat.nbr
    ports = np.arange(P)
    sender = nbr[:, ports ^ 1]
    hop = hop_table(n).astype(np.int16)
    rows = np.arange(N)
    rot = np.arange(PVQ, dtype=np.int32)
    rec_ab = np.stack([lat.rec_a, lat.rec_b], 1).astype(np.int16)
    rec = np.zeros((N, PVQ, n), np.int16)       # queue slot (p, v, q) flat
    birth = np.full((N, PVQ), -1, np.int32)
    credit = np.full((N, PV), credits, np.int64)  # queue (p, v) flat
    backlog = np.zeros(N, np.int64)
    c = dict(delivered=0, injected=0, lat_sum=0, lat_cnt=0, dropped=0)
    vc_del = np.zeros(V, np.int64)
    vc_inj = np.zeros(V, np.int64)
    link_use = np.zeros((N, P), np.int64)
    hist = np.zeros(hist_bins, np.int64)
    tl = {k: np.zeros(slots, np.int64) for k in
          ("delivered", "injected", "dropped", "in_flight", "dead_crossings")}
    tl_hist = np.zeros((slots, hist_bins), np.int64)
    qid = np.arange(PVQ) // Q                     # queue of each slot
    for slot in range(slots):
        ok = link_ok[slot]
        counted = slot >= warmup
        # every queued packet chooses (out-port, lane) against the credits
        # of the queue it would enter at the receiver
        want_port = np.full((N, PVQ), P, np.int64)
        want_lane = np.zeros((N, PVQ), np.int64)
        s_idx, k_idx = np.nonzero(birth >= 0)
        down = credit.reshape(N, P, V)[nbr, ports[None]]     # (N, P, V)
        sp, sl = vc_select(rec[s_idx, k_idx], ok[s_idx], down[s_idx], slot)
        want_port[s_idx, k_idx] = sp
        want_lane[s_idx, k_idx] = sl
        key = tr["prio"][slot].astype(np.int32) * PVQ + (rot + slot) % PVQ
        widx = np.zeros((N, P), np.int64)
        whas = np.zeros((N, P), bool)
        for p in range(P):
            k = np.where(want_port == p, key, INT_MAX)
            widx[:, p] = k.argmin(1)
            whas[:, p] = (k[rows, widx[:, p]] < INT_MAX) & ok[:, p]
        wlane = want_lane[rows[:, None], widx]
        has = whas[sender, ports]
        wi = widx[sender, ports]
        in_rec = rec[sender, wi]
        in_birth = birth[sender, wi]
        in_lane = wlane[sender, ports]
        rec_after = in_rec - hop[None]
        done = ~rec_after.any(-1)
        deliver = has & done
        tgt = ports[None] * V + in_lane
        need = np.where((wi // Q == tgt) | (in_lane > 0), 1, 2)
        vac = np.zeros((N, PV), np.int64)
        acc = np.zeros((N, P), bool)
        for p in range(P):
            t = tgt[:, p]
            acc[:, p] = (has[:, p] & ~done[:, p]
                         & (credit[rows, t] + vac[rows, t] >= need[:, p]))
            leaves = whas[:, p] & (deliver[nbr[:, p], p] | acc[nbr[:, p], p])
            vac[rows, widx[:, p] // Q] += leaves
        moved = deliver | acc
        dep = whas & moved[nbr, ports[None]]
        s_idx, p_idx = np.nonzero(dep)
        w_idx = widx[s_idx, p_idx]
        birth[s_idx, w_idx] = -1
        np.add.at(credit, (s_idx, w_idx // Q), 1)
        link_use += dep
        m_idx, p_idx = np.nonzero(acc)
        t = tgt[m_idx, p_idx]
        free = birth.reshape(N, PV, Q)[m_idx, t] < 0
        first = t * Q + free.argmax(1)
        rec[m_idx, first] = rec_after[m_idx, p_idx]
        birth[m_idx, first] = in_birth[m_idx, p_idx]
        credit[m_idx, t] -= 1
        # injection after transit, on the lane its local credits allow
        di = tr["di"][slot]
        r = rec_ab[di, tr["coin"][slot]]
        ip, il = vc_select(r, ok, credit.reshape(N, P, V), slot)
        iq = np.minimum(ip, P - 1) * V + il
        want_new = want(tr["u"][slot], load)
        can = ((want_new | (backlog > 0)) & (credit[rows, iq] >= 2)
               & (di != 0) & (ip < P))
        s_idx = np.nonzero(can)[0]
        q = iq[s_idx]
        free = birth.reshape(N, PV, Q)[s_idx, q] < 0
        last = q * Q + Q - 1 - free[:, ::-1].argmax(1)
        rec[s_idx, last] = r[s_idx]
        birth[s_idx, last] = slot
        credit[s_idx, q] -= 1
        backlog = np.clip(backlog + want_new - can, 0, 1 << 30)
        age = slot + 1 - in_birth
        meas = deliver & (in_birth >= warmup)
        c["lat_sum"] += int(age[meas].sum())
        c["lat_cnt"] += int(meas.sum())
        if counted:
            c["delivered"] += int(deliver.sum())
            c["injected"] += int(can.sum())
            vc_del += np.bincount((wi // Q % V)[deliver], minlength=V)
            vc_inj += np.bincount(il[can], minlength=V)
        if hist_bins:
            hist += _hist(age, meas, hist_bins)
            tl_hist[slot] = hist
        tl["delivered"][slot] = c["delivered"]
        tl["injected"][slot] = c["injected"]
        tl["dropped"][slot] = c["dropped"]
        tl["in_flight"][slot] = int((birth >= 0).sum())
        tl["dead_crossings"][slot] = int((dep & ~ok).sum())
    c["in_flight"] = int((birth >= 0).sum())
    c["vc_delivered"] = vc_del
    c["vc_injected"] = vc_inj
    c["vc_in_flight"] = (birth.reshape(N, P, V, Q) >= 0).sum((0, 1, 3))
    c["link_use"] = link_use
    if hist_bins:
        c["latency_hist"] = hist
        tl["lat_hist"] = tl_hist
    c["timeline"] = tl
    return c


def link_events_mask(lat: Lattice, slots: int, events) -> np.ndarray:
    """(slots, N, P) liveness of the channels under undirected link
    events ``((u, p), down_at, up_at)``: link (u, p) and its reverse
    (nbr[u, p], p ^ 1) fail from slot `down_at` and carry traffic again
    from `up_at` (None: not within the run).  Failures and repairs apply
    in slot order, those of one slot in the order listed, each failure
    before its own repair; from its slot on, each sets the link's state,
    so repairing a live link or failing a dead one changes nothing."""
    timed = sorted(((at, live, link) for link, down_at, up_at in events
                    for at, live in ((down_at, False), (up_at, True))
                    if at is not None), key=lambda ev: ev[0])
    ok = np.ones((slots, lat.N, lat.P), bool)
    for at, live, (u, p) in timed:
        ok[at:, u, p] = ok[at:, lat.nbr[u, p], p ^ 1] = live
    return ok


def link_flap_mask(lat: Lattice, slots: int, link, down_at: int,
                   up_at: int) -> np.ndarray:
    """(slots, N, P) liveness of a single undirected link (u, p) that is
    down from slot `down_at` until `up_at`: both directions, (u, p) and
    (nbr[u, p], p ^ 1), carry nothing in between."""
    return link_events_mask(lat, slots, [(link, down_at, up_at)])
