"""The plain reference simulator: one workload row under one scheduling
policy, cycle by cycle, in plain Python loops over sources, channels, banks
and buffer entries.

It is written from the simulator's stated semantics (the paper's three
stages for SMS; the centralized request buffer with each policy's admission
and pick rule for the others; the DRAM timing, energy and latency-histogram
accounting), not from the program's code: no array formulation, no
incrementally maintained counters where a recount says the same (PAR-BS's
group ranks and batch counts, SMS's batch lengths are recounted every cycle
they are read), and nothing imported from the program. Only what the
program states as its arithmetic is kept to the bit: the uint32 LCG, f32
adds, products and correctly rounded divides. Each f32 operation is done
in Python's float64 and rounded once to f32 (`f32`); for +, -, * and / of
f32 operands that equals IEEE f32 arithmetic, since 53 >= 2*24 + 2.

`simulate(fields, policy, pool, active, n_cycles, warmup)` returns, per
source, the statistics the program's drivers report for a row: the same
keys, delta-measured over the `n_cycles` after `warmup`.
"""
from __future__ import annotations

import struct
from collections import deque
from typing import Any, Dict, List

import numpy as np

M32 = 0xFFFFFFFF
RING = 64                    # completion ring: longer than any access
NEG_T = -100_000             # "long ago" for the four-activate window
AGE_CAP = (1 << 14) - 1
HIT_BIT = 1 << 14
RANK_SHIFT = 15
POL_BIT = 1 << 22
URGENT_BIT = POL_BIT << 1
CPU, GPU, HWA = 0, 1, 2
POLICIES = ("frfcfs", "atlas", "parbs", "tcm", "sms", "sms_dash", "bliss",
            "squash_prio")


_F32 = struct.Struct("f")


def f32(x: float) -> float:
    """x rounded to the nearest float32 (ties to even)."""
    return _F32.unpack(_F32.pack(x))[0]


def f32sum(xs: List[float]) -> float:
    """An f32 sum over channels, added as pairs of pairs."""
    xs = list(xs)
    while len(xs) > 1:
        xs = [f32(a + b) for a, b in zip(xs[::2], xs[1::2])] + \
            xs[len(xs) - len(xs) % 2:]
    return xs[0] if xs else 0.0


def lcg(x: int):
    """One step of the sources' uint32 LCG: (new state, u in [0, 1))."""
    x = (x * 1664525 + 1013904223) & M32
    return x, (x >> 8) / 16777216.0


def stable_rank(keys: List[float]) -> List[int]:
    """Ascending rank of each key, ties broken by index (0 = smallest)."""
    order = sorted(range(len(keys)), key=lambda i: (keys[i], i))
    rank = [0] * len(keys)
    for r, i in enumerate(order):
        rank[i] = r
    return rank


def release_offset(s: int, frame: int, jitter: int) -> int:
    """A frame's release jitter, hashed from (source, frame index)."""
    mix = ((s * 2654435761) & M32) ^ ((frame * 2246822519) & M32)
    h = (mix * 1664525 + 1013904223) & M32
    return (h >> 8) % (jitter + 1)


class System:
    """Sources, the DRAM channels and their accounting for one row."""

    def __init__(self, f: Dict[str, Any], pool: Dict[str, np.ndarray],
                 active: np.ndarray):
        self.f = f
        tm = f["timing"]
        self.lat_hit = tm["t_cas"]
        self.lat_conflict = tm["t_rp"] + tm["t_rcd"] + tm["t_cas"]
        self.lat_closed = tm["t_rcd"] + tm["t_cas"]
        self.t_faw, self.t_burst = tm["t_faw"], tm["t_burst"]
        self.S = S = f["n_cpu"] + f["n_gpu"] + f["n_hwa"]
        self.C, self.B = C, B = f["n_channels"], f["n_banks"]
        col = lambda k, typ: [typ(v) for v in np.asarray(pool[k])]
        self.cls = col("src_class", int)
        self.is_gpu = col("is_gpu", bool)
        self.rbl = col("rbl", float)
        self.ipm = col("inst_per_miss", float)
        self.blp = col("blp", int)
        self.dl_period = col("dl_period", int)
        self.dl_reqs = col("dl_reqs", int)
        self.dl_jitter = col("dl_jitter", int)
        self.active = [s for s in range(S) if bool(active[s])]
        self.mshr = [f["gpu_mshr"] if c == GPU else
                     f["hwa_mshr"] if c == HWA else f["cpu_mshr"]
                     for c in self.cls]
        self.ipc = f32(f["cpu_ipc"])
        # sources
        self.insts_acc = [0.0] * S
        self.insts_done = [0.0] * S
        self.outstanding = [0] * S
        self.emitted = [0] * S
        self.completed = [0] * S
        self.sum_lat = [0.0] * S
        self.pend = [None] * S         # (global bank, row, birth) or None
        self.cur_bank = [0] * S
        self.cur_row = [0] * S
        self.bank_ptr = [0] * S
        self.rng = [(s * 2654435761 + 12345) & M32 for s in range(S)]
        self.period_done = [0] * S
        self.dl_met = [0] * S
        self.dl_missed = [0] * S
        self.frames = [0] * S
        # DRAM
        self.bank_free = [[0] * B for _ in range(C)]
        self.open_row = [[-1] * B for _ in range(C)]
        self.acts = [[NEG_T] * 4 for _ in range(C)]
        self.bus_free = [0] * C
        self.ring = [[0] * S for _ in range(RING)]
        self.hits = [0] * S
        self.issued = [0] * S
        # energy
        self.e_act = [0.0] * S
        self.e_rw = [0.0] * S
        self.sb = [0] * C
        self.pdc = [0] * C
        self.e_wake = [0.0] * C
        self.pd_down = [False] * C
        self.busy_until = [0] * C
        # latency histogram
        self.bins, self.bin_w = f["lat_bins"], f["lat_bin_width"]
        self.hist = [[0] * self.bins for _ in range(S)]
        self._cycle_issues: List = []

    # -- the front half of a cycle -----------------------------------------
    def begin(self, t: int) -> None:
        f, S = self.f, self.S
        slot = self.ring[t % RING]
        for s in range(S):
            d = slot[s]
            if d:
                self.outstanding[s] -= d
                self.completed[s] += d
                self.period_done[s] += d
                slot[s] = 0
        if f["energy_enabled"]:
            for c in range(self.C):
                if t - self.busy_until[c] >= f["energy_pd_idle"]:
                    self.pd_down[c] = True
                if self.pd_down[c]:
                    self.pdc[c] += 1
                else:
                    self.sb[c] += 1
        for s in range(S):
            p = self.dl_period[s]
            if p > 0 and t > 0 and t % p == 0:
                self.frames[s] += 1
                if self.period_done[s] >= self.dl_reqs[s]:
                    self.dl_met[s] += 1
                else:
                    self.dl_missed[s] += 1
                self.period_done[s] = 0
        nbt = self.C * self.B
        n_rows = f["n_rows"]
        for s in self.active:
            cls = self.cls[s]
            free = self.pend[s] is None and \
                self.outstanding[s] < self.mshr[s]
            if cls == CPU:
                if free:
                    self.insts_acc[s] = f32(self.insts_acc[s] + self.ipc)
                    self.insts_done[s] = f32(self.insts_done[s] + self.ipc)
                want = free and self.insts_acc[s] >= self.ipm[s]
            elif cls == GPU:
                want = free
            else:
                p = max(self.dl_period[s], 1)
                want = free and t % p >= release_offset(
                    s, t // p, self.dl_jitter[s]) and \
                    self.period_done[s] + self.outstanding[s] \
                    < self.dl_reqs[s]
            x, u = lcg(self.rng[s])
            x, u2 = lcg(x)
            self.rng[s] = x
            if not want:
                continue
            if u < self.rbl[s]:
                bank, row = self.cur_bank[s], self.cur_row[s]
            else:
                self.bank_ptr[s] += 1
                bank = ((s * 3) % nbt + self.bank_ptr[s]
                        % max(self.blp[s], 1)) % nbt
                row = int(f32(u2 * n_rows))
                self.cur_bank[s], self.cur_row[s] = bank, row
            self.pend[s] = (bank, row, t)
            if cls == CPU:
                self.insts_acc[s] = f32(self.insts_acc[s] - self.ipm[s])
            self.emitted[s] += 1
            self.outstanding[s] += 1

    # -- DRAM --------------------------------------------------------------
    def check(self, c: int, b: int, row: int, t: int):
        """(may issue, access latency, row hit) of a request to bank b of
        channel c at cycle t."""
        orow = self.open_row[c][b]
        hit = orow == row
        lat = self.lat_hit if hit else \
            self.lat_closed if orow < 0 else self.lat_conflict
        ok = self.bank_free[c][b] <= t and \
            (hit or t - min(self.acts[c]) >= self.t_faw) and \
            t + lat >= self.bus_free[c]
        return ok, lat, hit

    def issue(self, c: int, b: int, row: int, src: int, birth: int,
              lat: int, hit: bool, t: int) -> None:
        done = t + lat + self.t_burst
        self.bank_free[c][b] = done
        self.open_row[c][b] = row
        if not hit:
            acts = self.acts[c]
            acts[acts.index(min(acts))] = t
        self.bus_free[c] = done
        self.ring[done % RING][src] += 1
        self.issued[src] += 1
        if hit:
            self.hits[src] += 1
        if self.f["energy_enabled"]:
            if self.pd_down[c]:
                self.e_wake[c] = f32(self.e_wake[c]
                                     + f32(self.f["energy_wake"]))
            self.pd_down[c] = False
            self.busy_until[c] = max(self.busy_until[c], done)
        if self.f["qos_enabled"]:
            self.hist[src][min((done - birth) // self.bin_w,
                               self.bins - 1)] += 1
        self._cycle_issues.append((src, done - birth, hit))

    def end(self) -> None:
        """Per-source f32 sums of this cycle's issues: the channels' terms
        are summed first, then added once, as a cycle's issues land."""
        if not self._cycle_issues:
            return
        per: Dict[int, List[int]] = {}
        for src, lat, hit in self._cycle_issues:
            a = per.setdefault(src, [0, 0, 0])
            a[0] += lat
            a[1] += 1
            a[2] += not hit
        self._cycle_issues = []
        e_rw, e_act = f32(self.f["energy_rw"]), f32(self.f["energy_act"])
        for src, (lat, n, miss) in per.items():
            self.sum_lat[src] = f32(self.sum_lat[src] + lat)
            if self.f["energy_enabled"]:
                self.e_rw[src] = f32(self.e_rw[src] + f32(n * e_rw))
                if miss:
                    self.e_act[src] = f32(self.e_act[src]
                                          + f32(miss * e_act))


# ---------------------------------------------------------------------------
# centralized request buffer: FR-FCFS and the schedulers built on it
# ---------------------------------------------------------------------------

class Centralized:
    """One request buffer of `buf_entries` slots per channel. Each cycle
    every channel admits at most one pending request (oldest first, into
    its lowest free slot; the GPU may hold at most `gpu_cap` slots of a
    channel), then issues the eligible entry of highest score (lowest slot
    among equals). The score is [policy priority] + row hit + age."""

    name = "frfcfs"

    def __init__(self, sim: System):
        self.sim = sim
        f = sim.f
        self.E = f["buf_entries"]
        self.gpu_cap = max(1, int(f["buf_entries"] * (1.0 - f["cpu_reserve"])))
        C = sim.C
        self.buf: List[List] = [[None] * self.E for _ in range(C)]
        self.gpu_occ = [0] * C
        self.pri = [0] * sim.S
        self.urgent_adm = [0] * sim.S

    def admit_key(self, s: int, birth: int) -> int:
        return birth

    def admit(self, t: int) -> None:
        sim, C = self.sim, self.sim.C
        best = [None] * C
        for s in range(sim.S):
            p = sim.pend[s]
            if p is None:
                continue
            c = p[0] % C
            if sim.is_gpu[s] and self.gpu_occ[c] >= self.gpu_cap:
                continue
            k = self.admit_key(s, p[2])
            if best[c] is None or k < best[c][0]:
                best[c] = (k, s)
        for c in range(C):
            if best[c] is None or None not in self.buf[c]:
                continue
            s = best[c][1]
            bank, row, birth = sim.pend[s]
            slot = self.buf[c].index(None)
            # entry: [src, bank in channel, row, birth, marked]
            self.buf[c][slot] = [s, bank // C, row, birth, False]
            if sim.is_gpu[s]:
                self.gpu_occ[c] += 1
            sim.pend[s] = None
            self.on_admit(s, t)

    def on_admit(self, s: int, t: int) -> None:
        pass

    def maintain(self, t: int) -> None:
        """Policy bookkeeping between admission and the pick."""

    def score(self, e, hit: bool, t: int) -> int:
        return self.pri[e[0]] + (HIT_BIT if hit else 0) + \
            min(t - e[3], AGE_CAP)

    def tick(self, t: int) -> None:
        self.admit(t)
        self.maintain(t)

    def select(self, t: int) -> None:
        sim = self.sim
        issues = []
        for c in range(sim.C):
            best, pick = -1, None
            # the per-channel and per-bank halves of `System.check`
            faw_ok = t - min(sim.acts[c]) >= sim.t_faw
            bus_free = sim.bus_free[c]
            ready = [bf <= t for bf in sim.bank_free[c]]
            open_row = sim.open_row[c]
            for slot, e in enumerate(self.buf[c]):
                if e is None or not ready[e[1]]:
                    continue
                orow = open_row[e[1]]
                hit = orow == e[2]
                if hit:
                    lat = sim.lat_hit
                elif not faw_ok:
                    continue
                else:
                    lat = sim.lat_closed if orow < 0 else sim.lat_conflict
                if t + lat < bus_free:
                    continue
                sc = self.score(e, hit, t)
                if sc > best:
                    best, pick = sc, (slot, e, lat, hit)
            if pick is not None:
                issues.append((c,) + pick)
        for c, slot, e, lat, hit in issues:
            sim.issue(c, e[1], e[2], e[0], e[3], lat, hit, t)
            self.on_issue(c, e, t)
            self.buf[c][slot] = None
            if sim.is_gpu[e[0]]:
                self.gpu_occ[c] -= 1

    def on_issue(self, c: int, e, t: int) -> None:
        pass


class ATLAS(Centralized):
    """Least attained service first: every `atlas_epoch` cycles each
    source's attained service decays by `atlas_alpha` and takes the
    epoch's issues; the least-served source ranks highest."""

    name = "atlas"

    def __init__(self, sim):
        super().__init__(sim)
        self.attained = [0.0] * sim.S
        self.served = [0] * sim.S

    def maintain(self, t):
        f, S = self.sim.f, self.sim.S
        if t % f["atlas_epoch"]:
            return
        a = f32(f["atlas_alpha"])
        self.attained = [f32(f32(a * x) + y)
                         for x, y in zip(self.attained, self.served)]
        self.served = [0] * S
        self.pri = [(S - r) << RANK_SHIFT
                    for r in stable_rank(self.attained)]

    def on_issue(self, c, e, t):
        self.served[e[0]] += 1


class PARBS(Centralized):
    """Parallelism-aware batching: when no marked request is left, mark the
    `parbs_cap` oldest requests of each (source, bank) of every channel;
    marked requests go first, and among sources fewest-marked first."""

    name = "parbs"

    def maintain(self, t):
        sim, cap, S = self.sim, self.sim.f["parbs_cap"], self.sim.S
        entries = [e for b in self.buf for e in b if e is not None]
        if not any(e[4] for e in entries):
            for b in self.buf:
                live = [e for e in b if e is not None]
                for e in live:
                    older = sum(1 for o in live if o[0] == e[0] and
                                o[1] == e[1] and o[3] < e[3])
                    e[4] = older < cap
        left = [0] * S
        for e in entries:
            if e[4]:
                left[e[0]] += 1
        self.pri = [(S - r) << RANK_SHIFT for r in stable_rank(left)]

    def score(self, e, hit, t):
        return (POL_BIT if e[4] else 0) + super().score(e, hit, t)


class TCM(Centralized):
    """Thread clustering: every `tcm_quantum` cycles the least intense
    sources holding at most `tcm_lat_frac` of the quantum's issues form
    the latency cluster, first, ranked by intensity; the rest rotate their
    ranks by one every quantum."""

    name = "tcm"

    def __init__(self, sim):
        super().__init__(sim)
        self.served = [0] * sim.S
        self.shuffle = 0

    def maintain(self, t):
        f, S = self.sim.f, self.sim.S
        if t % f["tcm_quantum"]:
            return
        inten = [float(x) for x in self.served]
        order = stable_rank(inten)
        total = max(sum(inten), 1.0)
        limit = f32(f32(f["tcm_lat_frac"]) * total)
        cum, lat_sorted = 0.0, []
        for x in sorted(inten):
            cum += x
            lat_sorted.append(cum <= limit)
        self.shuffle += 1
        self.pri = []
        for s in range(S):
            is_lat = lat_sorted[order[s]]
            rank = order[s] if is_lat else (order[s] + self.shuffle) % S
            self.pri.append((POL_BIT if is_lat else 0)
                            + ((S - rank) << RANK_SHIFT))
        self.served = [0] * S

    def on_issue(self, c, e, t):
        self.served[e[0]] += 1


class BLISS(Centralized):
    """Blacklisting: a source served `bliss_threshold` times in a row on a
    channel is blacklisted until the next clear, every
    `bliss_clear_interval` cycles; non-blacklisted sources go first."""

    name = "bliss"

    def __init__(self, sim):
        super().__init__(sim)
        self.last = [-1] * sim.C
        self.streak = [0] * sim.C
        self.black = [False] * sim.S
        self.pri = [POL_BIT] * sim.S

    def maintain(self, t):
        if t % self.sim.f["bliss_clear_interval"] == 0:
            self.black = [False] * self.sim.S
            self.pri = [POL_BIT] * self.sim.S

    def on_issue(self, c, e, t):
        s = e[0]
        streak = self.streak[c] + 1 if s == self.last[c] else 1
        self.last[c] = s
        if streak >= self.sim.f["bliss_threshold"]:
            self.black[s] = True
            self.pri[s] = 0
            streak = 0
        self.streak[c] = streak


class Squash(Centralized):
    """Probabilistic priority: every `squash_epoch` cycles each source draws
    a priority bit (accelerators with `squash_pb`, the GPU with
    `squash_gpu_pb`, CPUs with `squash_cpu_pb`); an accelerator behind its
    frame pace plus `squash_lead` is urgent, above everything, and its
    pending request admits ahead of older ones."""

    name = "squash_prio"

    def __init__(self, sim):
        super().__init__(sim)
        S = sim.S
        self.rng = [(s * 747796405 + 2891336453) & M32 for s in range(S)]
        self.prio = [False] * S
        self.urgent = [False] * S

    def admit_key(self, s, birth):
        return birth - ((1 << 20) if self.urgent[s] else 0)

    def on_admit(self, s, t):
        if self.urgent[s]:
            self.urgent_adm[s] += 1

    def maintain(self, t):
        sim, f = self.sim, self.sim.f
        if t % f["squash_epoch"] == 0:
            for s in range(sim.S):
                self.rng[s], u = lcg(self.rng[s])
                p = f["squash_pb"] if sim.cls[s] == HWA else \
                    f["squash_gpu_pb"] if sim.cls[s] == GPU else \
                    f["squash_cpu_pb"]
                self.prio[s] = u < f32(p)
        for s in range(sim.S):
            per, reqs = sim.dl_period[s], sim.dl_reqs[s]
            done = sim.period_done[s]
            self.urgent[s] = sim.cls[s] == HWA and per > 0 and \
                reqs - done > 0 and \
                done * per < (t % max(per, 1) + f["squash_lead"]) * reqs
            self.pri[s] = (URGENT_BIT if self.urgent[s] else 0) + \
                (POL_BIT if self.prio[s] else 0)


# ---------------------------------------------------------------------------
# SMS: the paper's staged scheduler
# ---------------------------------------------------------------------------

class SMS:
    """Stage 1: each source's requests queue in its own FIFO of `fifo_size`
    per channel; the run of same-(bank, row) requests at the front is a
    batch, ready when a different request follows it, its oldest request
    is `batch_age_cap` cycles old, or the FIFO is full. Stage 2, per
    channel: an idle channel picks a ready batch, shortest job first (the
    source with the fewest requests in flight) with probability
    `sjf_prob`, else round robin, and moves it one request per cycle into
    stage 3. Stage 3: per-bank FIFOs of `dcs_size`; each cycle the first
    eligible bank head in round-robin order issues. With `dash`, an
    accelerator whose frame slack is below its remaining requests times
    `dash_svc_est` preempts the pick, least slack first."""

    def __init__(self, sim: System, dash: bool):
        self.sim, self.dash = sim, dash
        C, S, B = sim.C, sim.S, sim.B
        self.fifo = [[deque() for _ in range(S)] for _ in range(C)]
        self.dcs = [[deque() for _ in range(B)] for _ in range(C)]
        self.drain_src = [-1] * C
        self.drain_left = [0] * C
        self.rr = [0] * C
        self.rr_bank = [0] * C
        self.rng = [((c + 1) * 40503) & M32 for c in range(C)]
        self.urgent_adm = [0] * S

    def tick(self, t: int) -> None:
        sim, f = self.sim, self.sim.f
        C, S, F = sim.C, sim.S, f["fifo_size"]
        for s in range(S):
            p = sim.pend[s]
            if p is not None and len(self.fifo[p[0] % C][s]) < F:
                self.fifo[p[0] % C][s].append((p[0] // C, p[1], p[2]))
                sim.pend[s] = None
        inflight = [e - d for e, d in zip(sim.emitted, sim.completed)]
        urgent_slack = self._dash_slack(t) if self.dash else {}
        for c in range(C):
            run, ready = [0] * S, [False] * S
            for s in range(S):
                q = self.fifo[c][s]
                if not q:
                    continue
                n = 0
                while n < len(q) and q[n][:2] == q[0][:2]:
                    n += 1
                run[s] = n
                ready[s] = n < len(q) or len(q) >= F or \
                    t - q[0][2] >= f["batch_age_cap"]
            self.rng[c], u = lcg(self.rng[c])
            use_sjf = u < f32(f["sjf_prob"])
            cand = [s for s in range(S) if ready[s]]
            if self.drain_left[c] <= 0 and cand:
                urgent = [s for s in cand if s in urgent_slack]
                if urgent:
                    pick = min(urgent, key=lambda s: (urgent_slack[s], s))
                elif use_sjf:
                    pick = min(cand, key=lambda s: (inflight[s], s))
                else:
                    pick = min(cand, key=lambda s: ((s - self.rr[c]) % S, s))
                    self.rr[c] = (pick + 1) % S
                self.drain_src[c], self.drain_left[c] = pick, run[pick]
            if self.drain_left[c] > 0:
                s = min(max(self.drain_src[c], 0), S - 1)
                q = self.fifo[c][s]
                if not q:
                    self.drain_left[c] = 0
                elif len(self.dcs[c][q[0][0]]) < f["dcs_size"]:
                    bank, row, birth = q.popleft()
                    self.dcs[c][bank].append((row, s, birth))
                    self.drain_left[c] -= 1

    def _dash_slack(self, t: int) -> Dict[int, float]:
        """{accelerator: slack} for those whose slack is negative."""
        sim, out = self.sim, {}
        for s in range(sim.S):
            per, left = sim.dl_period[s], sim.dl_reqs[s] - sim.period_done[s]
            if sim.cls[s] != HWA or per <= 0 or left <= 0:
                continue
            slack = f32(float(per - t % per)
                        - f32(left * f32(sim.f["dash_svc_est"])))
            if slack < 0.0:
                out[s] = slack
        return out

    def select(self, t: int) -> None:
        sim, B = self.sim, self.sim.B
        for c in range(sim.C):
            for k in range(B):
                b = (self.rr_bank[c] + k) % B
                q = self.dcs[c][b]
                if not q:
                    continue
                row, src, birth = q[0]
                ok, lat, hit = sim.check(c, b, row, t)
                if ok:
                    q.popleft()
                    sim.issue(c, b, row, src, birth, lat, hit, t)
                    self.rr_bank[c] = (b + 1) % B
                    break


CENTRALIZED = {p.name: p for p in (Centralized, ATLAS, PARBS, TCM, BLISS,
                                   Squash)}


def scheduler(policy: str, sim: System):
    if policy in CENTRALIZED:
        return CENTRALIZED[policy](sim)
    if policy in ("sms", "sms_dash"):
        return SMS(sim, dash=policy == "sms_dash")
    raise KeyError(f"the reference has no policy {policy!r}")


SNAP = ("insts_done", "emitted", "completed", "sum_lat", "dl_met",
        "dl_missed", "frames", "hits", "issued", "e_act", "e_rw", "sb",
        "pdc", "e_wake")


def simulate(fields: Dict[str, Any], policy: str, pool: Dict[str, Any],
             active, n_cycles: int, warmup: int) -> Dict[str, np.ndarray]:
    """Per-source statistics of one row: `n_cycles` measured after
    `warmup`, under the program's key names."""
    sim = System(fields, pool, active)
    sched = scheduler(policy, sim)

    def run(t0, t1):
        for t in range(t0, t1):
            sim.begin(t)
            sched.tick(t)
            sched.select(t)
            sim.end()

    run(0, warmup)
    snap = {k: list(getattr(sim, k)) for k in SNAP}
    hist0 = [list(h) for h in sim.hist]
    adm0 = list(sched.urgent_adm)
    run(warmup, warmup + n_cycles)

    cyc = float(n_cycles)
    d = lambda k: [f32(f32(a) - f32(b)) for a, b in
                   zip(getattr(sim, k), snap[k])]
    div = lambda a, b: [f32(x / y) for x, y in zip(a, b)]
    comp = d("completed")
    out = {
        "ipc": div(d("insts_done"), [cyc] * sim.S),
        "bw": div(comp, [cyc] * sim.S),
        "mpkc": [f32(x * 1000.0) for x in div(d("emitted"), [cyc] * sim.S)],
        "rbl": div(d("hits"), [max(x, 1.0) for x in d("issued")]),
        "avg_lat": div(d("sum_lat"), [max(x, 1.0) for x in comp]),
        "completed": comp,
        "emitted": d("emitted"),
        "outstanding_end": [float(x) for x in sim.outstanding],
        "inflight_unserved": [float(e - c) for e, c in
                              zip(sim.emitted, sim.completed)],
        "dl_met": d("dl_met"),
        "dl_missed": d("dl_missed"),
        "frames_released": d("frames"),
        "sim_steps": float(n_cycles),
    }
    if fields["qos_enabled"]:
        out["lat_hist"] = [[float(a - b) for a, b in zip(h, h0)]
                           for h, h0 in zip(sim.hist, hist0)]
    if policy == "squash_prio":
        out["urgent_admits"] = [float(a - b) for a, b in
                                zip(sched.urgent_adm, adm0)]
    if fields["energy_enabled"]:
        sb, pdc = sum(d("sb")), sum(d("pdc"))
        out.update({
            "energy_act": d("e_act"),
            "energy_rw": d("e_rw"),
            # one fused multiply-add, as XLA contracts this expression
            "energy_bg": f32(sb * f32(fields["energy_standby"])
                             + f32(pdc * f32(fields["energy_pd"]))),
            "energy_wake": f32sum(d("e_wake")),
            "pd_cycles": float(pdc),
        })
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


def simulate_rows(fields: Dict[str, Any], policy: str, pool, active,
                  n_cycles: int, warmup: int, rows) -> List[Dict]:
    """`simulate` of the given rows of a batch: one task of a worker."""
    return [simulate(fields, policy, {k: v[r] for k, v in pool.items()},
                     active[r], n_cycles, warmup) for r in rows]
