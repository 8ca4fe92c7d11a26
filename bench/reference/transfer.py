"""Plain reference of the transfer simulation the fabric sweep computes.

A straightforward discrete-event (fluid) simulator written from the
paper's description (Arslan & Kosar, arXiv:1708.05425): Fig. 3
partitioning, Algorithm 1 parameters, the SC / MC (Alg. 2) / ProMC
(Alg. 3) controllers, and the Globus Online and untuned baselines. It
imports nothing of the program: it takes plain
data (a network's numbers, the file sizes, the scheduler and its limits)
and returns each row's throughput and completion time.

Every floating quantity is made through ``num``: ``float`` (IEEE double,
the precision the program states) for the reference, ``numpy.float32``
for the lower-precision control.

A row ("task") is a dict::

    {"network": {bandwidth, rtt, buffer_size, unhidden_overhead,
                 channel_setup_cost, stream_cpu_overhead,
                 window_efficiency, max_streams_per_channel,
                 control_rtt, bandwidth_profile,
                 disk: {streaming_rate, per_file_overhead, saturation_cc,
                        contention, per_channel_rate}},
     "files": [size, ...],              # bytes, in transfer order
     "algorithm": "sc" | "mc" | "promc" | "globus" | "untuned",
     "max_cc": int, "num_chunks": int, "tick_period": float}
"""
from __future__ import annotations

import collections
import math

EPS = 1e-12
MB = 1024 * 1024
MAX_PIPELINING = 4096
MAX_TIME = 48 * 3600.0

# size classes (Fig. 3), ordered by file size; ALL is an undivided dataset
SMALL, MEDIUM, LARGE, HUGE, ALL = range(5)
CLASS_LABELS = {
    1: [ALL],
    2: [SMALL, LARGE],
    3: [SMALL, MEDIUM, LARGE],
    4: [SMALL, MEDIUM, LARGE, HUGE],
}
# Alg. 2: channels are dealt round-robin in the order Huge, Small, Large,
# Medium (undivided datasets last)
RR_ORDER = [HUGE, SMALL, LARGE, MEDIUM, ALL]
# Alg. 3: ProMC's priority weights, larger for smaller files
PROMC_DELTA = {SMALL: 6.0, MEDIUM: 3.0, LARGE: 2.0, HUGE: 1.0, ALL: 2.0}
# Globus Online's presets (pp, p, cc) by average file size
GLOBUS_PRESETS = {"small": (20, 2, 2), "medium": (5, 4, 4), "large": (2, 6, 3)}


class Path:
    """The network path and end-system disks of one row."""

    def __init__(self, net: dict, num):
        d = net["disk"]
        self.bandwidth = num(net["bandwidth"])
        self.rtt = num(net["rtt"])
        ctl = net.get("control_rtt")
        self.control_rtt = self.rtt if ctl is None else num(ctl)
        self.buffer_size = num(net["buffer_size"])
        self.unhidden = num(net["unhidden_overhead"])
        self.setup_cost = num(net["channel_setup_cost"])
        self.stream_cpu = num(net["stream_cpu_overhead"])
        self.window_eff = num(net["window_efficiency"])
        self.max_streams = int(net["max_streams_per_channel"])
        self.disk_rate = num(d["streaming_rate"])
        self.file_overhead = num(d["per_file_overhead"])
        self.saturation = int(d["saturation_cc"])
        self.contention = num(d["contention"])
        lane = d.get("per_channel_rate")
        self.lane = (
            self.disk_rate / max(1, self.saturation)
            if lane is None else num(lane)
        )
        prof = net.get("bandwidth_profile")
        self.profile = (
            None if prof is None else [(num(t), num(m)) for t, m in prof]
        )

    def bandwidth_at(self, t):
        if self.profile is None:
            return self.bandwidth
        mult = self.profile[0][1]
        for step_t, step_m in self.profile:
            if step_t > t:
                break
            mult = step_m
        return self.bandwidth * mult

    def next_change(self, t):
        if self.profile is not None:
            for step_t, _ in self.profile:
                if step_t > t:
                    return step_t
        return math.inf

    def disk_aggregate(self, n: int):
        """Disk bandwidth shared by ``n`` transferring channels: flat up to
        the saturation point, then falling with contention."""
        if n <= 0:
            return 0.0
        over = max(0, n - self.saturation)
        return self.disk_rate * (1.0 / (1.0 + self.contention * over))

    def channel_cap(self, parallelism: int):
        """One channel's ceiling: ``p`` window-limited TCP streams (a CPU
        tax per extra stream, a server clamp on p), and one disk lane."""
        p = max(1, min(parallelism, self.max_streams))
        per_stream = self.window_eff * self.buffer_size / max(self.rtt, 1e-9)
        eff = 1.0 / (1.0 + self.stream_cpu * (p - 1))
        return min(min(p * per_stream * eff, self.bandwidth), self.lane)

    def file_dead_time(self, pipelining: int):
        """Per-file serial overhead: the command gap amortised over the
        pipeline, server processing, and the disk's per-file cost."""
        gap = self.control_rtt / (1.0 + pipelining)
        return gap + self.unhidden + self.file_overhead


def waterfill(caps, pool):
    """Max-min fair split of ``pool`` over entities capped at ``caps``."""
    n = len(caps)
    alloc = [0.0] * n
    if n == 0 or pool <= 0:
        return alloc
    remaining = pool
    unfilled = list(range(n))
    while unfilled and remaining > 1e-12:
        share = remaining / len(unfilled)
        capped = [i for i in unfilled if caps[i] <= share + 1e-12]
        if not capped:
            for i in unfilled:
                alloc[i] += share
            break
        for i in capped:
            alloc[i] = caps[i]
            remaining -= caps[i]
            unfilled.remove(i)
    return alloc


def algorithm1(avg, bdp, buffer_size, max_cc, num_files):
    """(pipelining, parallelism, concurrency) of Algorithm 1, capped at the
    chunk's file count."""
    pp = max(0, min(int(math.ceil(bdp / avg)), MAX_PIPELINING))
    par = max(1, min(int(math.ceil(bdp / buffer_size)),
                     int(math.ceil(avg / buffer_size))))
    cc = max(1, int(min(max(bdp / avg, 2.0), float(max_cc))))
    if num_files > 0:
        pp = min(pp, max(0, num_files - 1))
        cc = min(cc, num_files)
    return pp, par, cc


class Chunk:
    def __init__(self, ctype, sizes, params):
        self.ctype = ctype
        self.queue = collections.deque(sizes)
        self.total = sum(sizes)
        self.n_files = len(sizes)
        self.params = params
        self.queue_bytes = self.total
        self.delivered = 0.0
        self.delivered_at_tick = 0.0
        self.rate = 0.0
        self.done = False


class Channel:
    def __init__(self, chunk, params, dead):
        self.chunk = chunk
        self.params = params
        self.dead = dead
        self.remaining = 0.0
        self.busy = False


def make_chunks(task, path, num):
    """Partition (Fig. 3) and tune (Alg. 1), or one undivided chunk at the
    baseline's fixed parameters."""
    algo = task["algorithm"]
    sizes = list(task["files"])
    max_cc = task["max_cc"]
    if algo == "untuned":
        return [(ALL, sizes, (0, 1, 1))]
    if algo == "globus":
        avg = sum(sizes) / len(sizes) if sizes else 1.0
        cls = ("small" if avg < 50 * MB
               else "medium" if avg <= 250 * MB else "large")
        return [(ALL, sizes, GLOBUS_PRESETS[cls])]
    k = task["num_chunks"]
    cuts = [path.bandwidth / 20.0, path.bandwidth / 5.0, path.bandwidth]
    cuts = cuts[: k - 1]
    buckets = [[] for _ in CLASS_LABELS[k]]
    for s in sizes:
        i = next((j for j, t in enumerate(cuts) if s <= t), len(cuts))
        buckets[i].append(s)
    out = []
    bdp = path.bandwidth * path.rtt
    for ctype, b in zip(CLASS_LABELS[k], buckets):
        if b:
            avg = max(num(sum(b)) / len(b), 1.0)
            out.append((ctype, b, algorithm1(
                avg, bdp, path.buffer_size, max_cc, len(b))))
    return out


class Transfer:
    """One row: its chunks, channels and controller, stepped event by
    event."""

    def __init__(self, task: dict, num=float):
        self.num = num
        self.path = Path(task["network"], num)
        self.algo = task["algorithm"]
        self.max_cc = task["max_cc"]
        self.tick = num(task["tick_period"])
        self.chunks = [
            Chunk(ct, sizes, params)
            for ct, sizes, params in make_chunks(task, self.path, num)
        ]
        self.channels = []
        self.t = num(0.0)
        self.next_tick = self.tick
        self.total_bytes = num(sum(c.total for c in self.chunks))
        self.sc_order = sorted(
            range(len(self.chunks)), key=lambda i: -self.chunks[i].ctype
        )
        self.sc_cursor = 0
        self.streak, self.streak_pair = 0, None
        self.apply(self.initial_actions())
        self.feed()

    # ---------------- controller view ---------------- #

    def open_channels(self, i):
        return [ch for ch in self.channels if ch.chunk == i]

    def eta(self, i):
        """Remaining bytes over the measured rate (the closed-form
        prediction before any measurement); 0 when done, inf when idle."""
        c = self.chunks[i]
        mine = self.open_channels(i)
        rem = c.queue_bytes + sum(ch.remaining for ch in mine if ch.busy)
        if c.done or rem <= 0:
            return 0.0, rem
        rate = c.rate
        if not rate > 0:
            rate = self.predicted_rate(c, max(len(mine), 1))
        if rate <= 0:
            return math.inf, rem
        return rem / rate, rem

    def predicted_rate(self, c, n):
        path, (pp, par, _) = self.path, c.params
        avg = max(self.num(c.total) / c.n_files, 1.0)
        total = max(1, len(self.channels))
        cap = path.channel_cap(par)
        pool = min(path.bandwidth, path.disk_aggregate(total))
        rate = min(cap, pool / max(1, total))
        t_file = path.file_dead_time(pp) + avg / max(rate, 1e-9)
        return n * avg / t_file

    # ---------------- controllers ---------------- #

    def initial_actions(self):
        chunks = self.chunks
        if self.algo in ("untuned", "globus"):
            return [("open", 0, chunks[0].params[2])]
        if self.algo == "sc":
            return self.sc_open_current()
        if self.algo == "mc":
            order = [i for ct in RR_ORDER
                     for i, c in enumerate(chunks) if c.ctype == ct]
            alloc = {i: 0 for i in order}
            for k in range(self.max_cc):
                alloc[order[k % len(order)]] += 1
            return [("open", i, n) for i, n in alloc.items() if n > 0]
        # ProMC: delta-weighted shares of maxCC; every chunk gets at least
        # one channel, leftovers go by largest fractional part
        live = list(range(len(chunks)))
        w = {i: PROMC_DELTA[chunks[i].ctype] * chunks[i].total for i in live}
        total = sum(w.values()) or 1.0
        shares = {i: w[i] / total * self.max_cc for i in live}
        alloc = {i: max(1, int(math.floor(shares[i]))) for i in live}
        budget = max(self.max_cc, len(live))
        while sum(alloc.values()) > budget:
            i = max(alloc, key=lambda j: (alloc[j], -shares[j]))
            if alloc[i] <= 1:
                break
            alloc[i] -= 1
        frac = sorted(live, key=lambda i: shares[i] - math.floor(shares[i]),
                      reverse=True)
        k = 0
        while sum(alloc.values()) < budget and frac:
            alloc[frac[k % len(frac)]] += 1
            k += 1
        return [("open", i, n) for i, n in alloc.items() if n > 0]

    def sc_open_current(self):
        while self.sc_cursor < len(self.sc_order):
            i = self.sc_order[self.sc_cursor]
            if self.chunks[i].n_files > 0:
                return [("open", i, self.chunks[i].params[2])]
            self.sc_cursor += 1
        return []

    def on_complete(self, cid):
        n_ch = len(self.open_channels(cid))
        if self.algo == "sc":
            self.sc_cursor += 1
            return [("close", cid, n_ch)] + self.sc_open_current()
        if self.algo not in ("mc", "promc"):
            return []
        if self.algo == "promc":
            self.streak, self.streak_pair = 0, None
        # freed channels go one by one to the chunk with the largest ETA,
        # whose ETA then shrinks by (n-1)/n
        etas, owners = {}, {}
        for i, c in enumerate(self.chunks):
            e, rem = self.eta(i)
            if not c.done and i != cid and rem > 0:
                etas[i], owners[i] = e, len(self.open_channels(i))
        if not etas:
            return []
        moves = {}
        for _ in range(n_ch):
            dst = max(etas, key=lambda i: etas[i])
            moves[dst] = moves.get(dst, 0) + 1
            n = owners[dst] + moves[dst]
            if math.isfinite(etas[dst]) and n > 0:
                etas[dst] *= (n - 1) / n if n > 1 else 0.5
        return [("move", cid, d, k) for d, k in moves.items()]

    def on_tick(self):
        """ProMC: a chunk at least twice as fast (by ETA) as another for
        three ticks running gives the slow one a channel."""
        live = []
        for i, c in enumerate(self.chunks):
            e, rem = self.eta(i)
            n = len(self.open_channels(i))
            if not c.done and rem > 0 and n > 0:
                live.append((i, e, n))
        if len(live) < 2:
            self.streak, self.streak_pair = 0, None
            return []
        fast = min(live, key=lambda v: v[1])
        slow = max(live, key=lambda v: v[1])
        if not math.isfinite(slow[1]) and self.chunks[slow[0]].rate == 0:
            return []
        imbalanced = (slow[1] >= 2.0 * fast[1] and fast[0] != slow[0]
                      and fast[2] > 1)
        pair = (fast[0], slow[0])
        if imbalanced and pair == self.streak_pair:
            self.streak += 1
        elif imbalanced:
            self.streak, self.streak_pair = 1, pair
        else:
            self.streak, self.streak_pair = 0, None
            return []
        if self.streak >= 3:
            self.streak, self.streak_pair = 0, None
            return [("move", fast[0], slow[0], 1)]
        return []

    # ---------------- channels ---------------- #

    def apply(self, actions):
        for act in actions:
            if act[0] == "open":
                for _ in range(act[2]):
                    self.open_channel(act[1], None)
            elif act[0] == "close":
                self.close_channels(act[1], act[2])
            else:
                _, src, dst, n = act
                for prev in self.close_channels(src, n):
                    self.open_channel(dst, prev)

    def open_channel(self, i, prev):
        """A new channel pays the set-up cost; a moved one whose stream
        count is unchanged reuses its connection at a quarter of it."""
        params = self.chunks[i].params
        cost = self.path.setup_cost
        if prev is not None and prev[1] == params[1]:
            cost = 0.25 * cost
        self.channels.append(Channel(i, params, cost))

    def close_channels(self, i, n):
        """Close up to ``n`` channels of chunk i, idle ones first; a file
        in flight goes back to the head of the queue with what is left."""
        mine = sorted(self.open_channels(i), key=lambda ch: ch.busy)[:n]
        for ch in mine:
            if ch.busy and ch.remaining > 0:
                size = int(math.ceil(ch.remaining))
                c = self.chunks[i]
                c.queue.appendleft(size)
                c.queue_bytes += size
        gone = set(map(id, mine))
        self.channels = [ch for ch in self.channels if id(ch) not in gone]
        return [ch.params for ch in mine]

    def feed(self):
        for ch in self.channels:
            c = self.chunks[ch.chunk]
            if not ch.busy and c.queue:
                size = c.queue.popleft()
                c.queue_bytes -= size
                ch.busy = True
                ch.remaining = self.num(size)
                ch.dead = ch.dead + self.path.file_dead_time(ch.params[0])

    # ---------------- stepping ---------------- #

    @property
    def done(self):
        return all(c.done for c in self.chunks)

    def transferring(self):
        return [ch for ch in self.channels if ch.busy and ch.dead <= EPS]

    def rates(self):
        moving = self.transferring()
        pool = min(self.path.bandwidth_at(self.t),
                   self.path.disk_aggregate(len(moving)))
        alloc = waterfill([self.path.channel_cap(ch.params[1])
                           for ch in moving], pool)
        got = dict(zip(map(id, moving), alloc))
        return [got.get(id(ch), 0.0) for ch in self.channels]

    def horizon(self, rates):
        dt = min(self.next_tick - self.t, self.path.next_change(self.t) - self.t)
        for ch, r in zip(self.channels, rates):
            if not ch.busy:
                continue
            if ch.dead > EPS:
                dt = min(dt, ch.dead)
            elif r > EPS:
                dt = min(dt, ch.remaining / r)
        return max(dt, 0.0)

    def step(self):
        if self.t > MAX_TIME:
            raise RuntimeError(f"transfer exceeded {MAX_TIME} s")
        rates = self.rates()
        dt = self.horizon(rates)
        if not any(ch.busy for ch in self.channels):
            held = {ch.chunk for ch in self.channels}
            if any(not c.done and i not in held
                   for i, c in enumerate(self.chunks)):
                raise RuntimeError("controller stranded a chunk")
        self.t = self.t + dt
        for ch, r in zip(self.channels, rates):
            if not ch.busy:
                continue
            if ch.dead > EPS:
                ch.dead = max(0.0, ch.dead - dt)
                continue
            if r > EPS and dt > 0:
                moved = min(ch.remaining, r * dt)
                ch.remaining = ch.remaining - moved
                c = self.chunks[ch.chunk]
                c.delivered = c.delivered + moved
            if ch.remaining <= EPS:
                ch.busy = False
                ch.remaining = 0.0
        self.feed()
        finished = []
        for i, c in enumerate(self.chunks):
            if not c.done and not c.queue and not any(
                ch.busy for ch in self.open_channels(i)
            ):
                c.done = True
                c.queue_bytes = 0
                finished.append(i)
        for i in finished:
            self.apply(self.on_complete(i))
            self.feed()
        if self.t >= self.next_tick - EPS:
            for c in self.chunks:
                inst = (c.delivered - c.delivered_at_tick) / self.tick
                c.delivered_at_tick = c.delivered
                c.rate = inst if c.rate == 0 else 0.5 * c.rate + 0.5 * inst
            if self.algo == "promc":
                self.apply(self.on_tick())
                self.feed()
            self.next_tick = self.next_tick + self.tick

    def result(self):
        total_time = max(self.t, EPS)
        return {
            "throughput": float(self.total_bytes / total_time),
            "total_time": float(total_time),
            "total_bytes": float(self.total_bytes),
        }


def simulate(tasks, num=float):
    """Results of every row, in input order."""
    out = []
    for task in tasks:
        tr = Transfer(task, num)
        while not tr.done:
            tr.step()
        out.append(tr.result())
    return out
