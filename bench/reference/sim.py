"""The plain reference: the simulator's semantics as a sequential loop.

One case at a time, one worker at a time, in plain Python: every queue is
a ``deque``, every worker a loop iteration, and every rule of the runtime
is written the way the paper states it (task stacks, XQueue's per-pair
FIFOs, the locked global queue, the steal-request cells, NA-RP's
redirected pushes, NA-WS's transfer loop, the barrier episode).  Nothing
is batched, padded, masked or vectorised, and nothing is imported from the
program.

A scheduling point is bulk-synchronous: within one phase every worker
sees the state as the phase began, and where two workers' writes meet,
the lower worker id goes first (a contended lock or atomic serialises in
worker order; a steal-request cell keeps the last, highest-id, writer; a
join that several finishers complete at once is claimed by the lowest).

A row is every field the program's ``SweepResult`` carries per case:
``time_ns``, ``steps``, ``completed``, each counter, and the SLO fields.
"""

from __future__ import annotations

import bisect
import math
from collections import deque

import numpy as np

CTR_NAMES = ("exec", "self", "local", "remote", "static_push", "imm_exec",
             "req_sent", "req_handled", "req_has_steal", "stolen",
             "stolen_local", "stolen_remote", "src_empty", "tgt_full",
             "atomic_ops", "busy_ns", "stolen_xnode", "xnode_bytes")
QUEUES = ("locked_global", "xqueue")
BARRIERS = ("centralized_count", "tree")
BALANCERS = ("static_rr", "na_rp", "na_ws")

K_SPAWN = 2     # pushes per worker per scheduling point
WS_CAP = 32     # most tasks one NA-WS transfer moves
NV_CAP = 24     # most steal requests per thief retry
MASK32 = 0xFFFFFFFF


def xorshift(s: int) -> int:
    s ^= (s << 13) & MASK32
    s ^= s >> 17
    s ^= (s << 5) & MASK32
    return s


class Machine:
    """Workers laid out over sockets (or over the flat model's zones):
    who is near whom, and what touching another worker's line costs."""

    def __init__(self, n: int, machine_cfg: dict | None, n_zones: int,
                 costs: dict):
        self.n = n
        self.costs = costs
        self.flat = machine_cfg is None
        if self.flat:
            self.zone = max(n // n_zones, 1)
            self.n_dom = None
            self.dist = None
        else:
            if machine_cfg.get("n_nodes", 1) != 1:
                raise NotImplementedError(
                    "the reference covers single-node machines")
            self.n_dom = machine_cfg["n_sockets"]
            self.dist = [list(map(int, r)) for r in machine_cfg["dist"]]
            self.zone = max(n // self.n_dom, 1)
        self._remote = [self._remote_table(w) for w in range(n)]

    def domain(self, w: int) -> int:
        """The socket (or flat zone) of worker ``w``; workers left over
        when ``n`` is not a multiple of the sockets join the last one."""
        if self.flat:
            return w // self.zone
        return min(w // self.zone, self.n_dom - 1)

    def same_domain(self, a: int, b: int) -> bool:
        return self.domain(a) == self.domain(b)

    def comm(self, a: int, b: int) -> int:
        """Latency of worker ``a`` touching a cache line of worker ``b``."""
        c = self.costs
        if a == b:
            return c["c_cache"]
        if self.flat:
            return c["c_zone"] if self.same_domain(a, b) else c["c_numa"]
        return self.dist[self.domain(a)][self.domain(b)]

    def _remote_table(self, w: int):
        """Candidates in other sockets with cumulative weights: the nearest
        socket's workers weigh ``1 + d_max - d_near``, the farthest 1."""
        if self.flat:
            return None
        dw = self.domain(w)
        cands = [j for j in range(self.n) if self.domain(j) != dw]
        if not cands:
            return [], []
        d = [self.dist[dw][self.domain(j)] for j in cands]
        dmax = max(d)
        cum, tot = [], 0
        for dj in d:
            tot += dmax - dj + 1
            cum.append(tot)
        return cands, cum

    def pick_victim(self, w: int, want_local: bool, draw: int) -> int:
        """A victim other than ``w``: in its own socket when
        ``want_local``, elsewhere by distance weight otherwise; a side
        with no candidate gives way to the other."""
        z = self.zone
        if self.flat:
            base = (w // z) * z
            off = draw % max(z - 1, 1)
            local = base + off + (1 if off >= w - base else 0)
            off_r = draw % max(self.n - z, 1)
            remote = off_r + z if off_r >= base else off_r
            has_local, has_remote = z > 1, self.n > z
        else:
            dom = self.domain(w)
            start = dom * z
            end = self.n if dom == self.n_dom - 1 else (dom + 1) * z
            off = draw % max(end - start - 1, 1)
            local = start + off + (1 if off >= w - start else 0)
            cands, cum = self._remote[w]
            has_local, has_remote = end - start > 1, bool(cands)
            remote = (cands[bisect.bisect_right(cum, draw % cum[-1])]
                      if cands else 0)
        use_local = want_local if (has_local and has_remote) else has_local
        return local if use_local else remote


def barrier_episode(barrier: str, n: int, machine_cfg: dict | None,
                    costs: dict) -> tuple:
    """(ns added to the makespan, atomic operations) of the final barrier.

    Centralized: every worker serialises on one line, 2(n-1) contended
    atomics.  Tree: n-1 atomics; the gather climbs a binary tree, within a
    socket at the intra-socket latency, then across sockets pairwise at
    the largest distance each level joins; the release mirrors it without
    atomics."""
    c = costs
    if barrier == "centralized_count":
        return 2 * (n - 1) * (c["c_atomic"] + c["c_contend"]), 2 * (n - 1)
    if machine_cfg is None:
        depth = max(1, math.ceil(math.log2(n)))
        return depth * (c["c_atomic"] + 2 * c["c_zone"]), n - 1
    s = machine_cfg["n_sockets"]
    dist = machine_cfg["dist"]
    zs = max(n // s, 1)
    used = min(-(-n // zs), s)
    width = max(zs, n - (s - 1) * zs)
    depth = math.ceil(math.log2(width)) if width > 1 else 0
    t = depth * (c["c_atomic"] + 2 * c["c_zone"])
    levels = 0
    span = 1
    while span < used:
        d = 0
        for i in range(0, used, 2 * span):
            for a in range(i, min(i + span, used)):
                for b in range(i + span, min(i + 2 * span, used)):
                    d = max(d, int(dist[a][b]))
        if d:
            t += c["c_atomic"] + 2 * d
            levels += 1
        span *= 2
    if depth + levels == 0:
        t = c["c_atomic"] + 2 * c["c_zone"]
    return t, n - 1


def slo(done_ns: list, n_tasks: int) -> dict:
    """Nearest-rank p50/p90/p99 of completion minus release (release 0 in a
    closed system), and completions per second over the busy span."""
    lat = sorted(d for d in done_ns[:n_tasks] if d >= 0)
    n = len(lat)
    if n == 0:
        return dict(p50_ns=-1, p90_ns=-1, p99_ns=-1,
                    throughput_tasks_per_s=0.0)

    def pct(q: float) -> int:
        return lat[max(int(math.ceil(q / 100.0 * n)) - 1, 0)]

    return dict(p50_ns=pct(50.0), p90_ns=pct(90.0), p99_ns=pct(99.0),
                throughput_tasks_per_s=n * 1e9 / max(lat[-1], 1))


class Case:
    """One case's whole state, stepped one scheduling point at a time."""

    def __init__(self, graph, case: dict, sim: dict, machine_cfg, costs,
                 exec_float: str):
        self.c = costs
        self.n = n = int(case["n_workers"])
        self.Q = sim["queue_cap"]
        self.S = sim["stack_cap"]
        self.max_steps = sim["max_steps"]
        self.m = Machine(n, machine_cfg, sim.get("n_zones", 8), costs)
        self.locked = case["queue"] == "locked_global"
        self.central = case["barrier"] == "centralized_count"
        self.narp = case["balance"] == "na_rp"
        self.naws = case["balance"] == "na_ws"
        self.pays_count = not self.locked and self.central
        self.n_victim = int(case["n_victim"])
        self.n_steal = int(case["n_steal"])
        self.t_interval = int(case["t_interval"])
        self.p_local = float(np.float32(case["p_local"]))
        self.dur = [int(x) for x in graph.dur]
        self.first_child = [int(x) for x in graph.first_child]
        self.n_children = [int(x) for x in graph.n_children]
        self.notify = [int(x) for x in graph.notify]
        self.T = len(self.dur)
        self._penalty_table(round(float(graph.mem_bound), 3), exec_float)

        seed = int(case["seed"]) & MASK32
        self.stack = [[] for _ in range(n)]       # [task, count] ranges
        self.stack[0].append([0, 1])              # the root task
        self.xq = [[deque() for _ in range(n)] for _ in range(n)]
        self.xq_tasks = 0
        self.gq = deque()                         # the locked global queue
        self.join_cnt = [int(x) for x in graph.join_dep]
        self.done_ns = [-1] * self.T
        self.creator = [0] * self.T
        self.clock = [0] * n
        self.rr = list(range(n))
        self.deq_rr = [0] * n
        self.idle = [0] * n
        self.rng = [(w * 2654435761 + seed * 40503 + 1) & MASK32
                    for w in range(n)]
        self.round = [1] * n                      # victim-owned cell
        self.req_round = [0] * n                  # thief-written cell
        self.req_tid = [-1] * n
        self.rp_tgt = [-1] * n
        self.rp_left = [0] * n
        self.ctr = dict.fromkeys(CTR_NAMES, 0)
        self.n_done = 0
        self.overflow = False
        self.steps = 0

    def _penalty_table(self, mem_bound: float, exec_float: str) -> None:
        """Duration multipliers of memory-bound tasks, in float32 (or the
        control's lower precision): 1 on the creator, the zone penalty in
        its socket, elsewhere the remote penalty scaled by distance over
        one interconnect hop."""
        f = {"float32": np.float32, "bfloat16": _bfloat16()}[exec_float]
        c = self.c
        self.mem_bound = mem_bound
        self.f = f
        one = f(1.0)

        def mult(pen):
            return one + f(mem_bound) * (pen - one)

        self.mult_self = mult(one)
        self.mult_zone = mult(f(c["exec_zone_penalty"]))
        m = self.m
        if m.flat:
            self.mult_far = None
            self.mult_remote = mult(f(c["exec_remote_penalty"]))
        else:
            k = f(c["exec_remote_penalty"] - 1.0)
            self.mult_far = [[mult(one + k * f(d) / f(c["c_numa"]))
                              for d in row] for row in m.dist]

    def exec_ns(self, task: int, cr: int, w: int) -> int:
        d = self.dur[task]
        if self.mem_bound <= 0:
            return d
        m = self.m
        if cr == w:
            mult = self.mult_self
        elif m.same_domain(cr, w):
            mult = self.mult_zone
        elif m.flat:
            mult = self.mult_remote
        else:
            mult = self.mult_far[m.domain(cr)][m.domain(w)]
        return int(self.f(d) * mult)

    # ---------------- helpers ----------------
    def push_stack(self, w: int, task: int, cnt: int) -> None:
        if len(self.stack[w]) < self.S:
            self.stack[w].append([task, cnt])
        else:
            self.overflow = True

    def finish(self, items: list) -> None:
        """Tasks ``(worker, task)`` complete at once: each finisher puts
        the task's children on its own stack, every join loses one
        dependency, and a join that reaches zero goes on the stack of the
        lowest-id worker that completed it."""
        for w, t in items:
            self.done_ns[t] = max(self.done_ns[t], self.clock[w])
            self.n_done += 1
            if self.n_children[t] > 0:
                self.push_stack(w, self.first_child[t], self.n_children[t])
        for _, t in items:
            j = self.notify[t]
            if j >= 0:
                self.join_cnt[j] -= 1
        claimed = set()
        for w, t in items:
            j = self.notify[t]
            if j >= 0 and self.join_cnt[j] == 0 and j not in claimed:
                claimed.add(j)
                self.creator[j] = w
                self.push_stack(w, j, 1)

    def contended_atomic(self, workers: list) -> None:
        """Simultaneous RMWs on one shared line serialise: the k-th pays
        k hand-offs on top of the atomic."""
        c = self.c
        for rank, w in enumerate(workers):
            self.clock[w] += c["c_atomic"] + rank * c["c_contend"]
        self.ctr["atomic_ops"] += len(workers)

    def running(self) -> bool:
        has_work = (self.xq_tasks > 0 or len(self.gq) > 0
                    or any(self.stack))
        return (self.n_done < self.T and self.steps < self.max_steps
                and not self.overflow and has_work)

    # ---------------- one scheduling point ----------------
    def step(self) -> None:
        if self.narp:
            self.adopt()
        for _ in range(K_SPAWN):
            self.spawn()
        found = self.dequeue()
        if self.narp or self.naws:
            self.thief(found)
            self.victim(found)
        self.execute(found)
        self.steps += 1

    def adopt(self) -> None:
        """NA-RP: a worker about to push is a victim too; a valid request
        is answered by adopting the thief if none is adopted yet."""
        for w in range(self.n):
            if self.stack[w] and self.req_round[w] == self.round[w]:
                if self.rp_tgt[w] < 0:
                    self.rp_tgt[w] = max(self.req_tid[w], 0)
                    self.rp_left[w] = self.n_steal
                self.round[w] += 1
                self.ctr["req_handled"] += 1

    def spawn(self) -> None:
        """Every worker with a stack entry pushes the next task of its top
        range: to the global queue under its lock, or to one XQueue (the
        round-robin target, or NA-RP's adopted thief).  A full XQueue makes
        the pusher run the task at once."""
        c, ctr, n = self.c, self.ctr, self.n
        pushers = [w for w in range(n) if self.stack[w]]
        imm = []
        rp_moved = [0] * n
        rp_failed = [False] * n
        for rank, w in enumerate(pushers):
            top = self.stack[w][-1]
            task = top[0]
            if self.locked:
                self.clock[w] += (c["c_atomic"] + c["c_pq_op"]
                                  + c["c_alloc"] + rank * c["c_lock"])
                self.gq.append((task, self.clock[w]))
                ctr["static_push"] += 1
                ctr["atomic_ops"] += 1
            else:
                use_rp = (self.narp and self.rp_tgt[w] >= 0
                          and self.rp_left[w] > 0)
                tgt = self.rp_tgt[w] if use_rp else self.rr[w] % n
                self.clock[w] += (c["c_alloc"] + c["c_slot"]
                                  + self.m.comm(w, tgt))
                q = self.xq[tgt][w]
                ok = len(q) < self.Q
                if ok:
                    q.append((task, self.clock[w]))
                    self.xq_tasks += 1
                if use_rp:
                    if ok:
                        rp_moved[w] = 1
                        ctr["stolen"] += 1
                        ctr["stolen_local" if self.m.same_domain(w, tgt)
                            else "stolen_remote"] += 1
                    else:
                        rp_failed[w] = True
                        ctr["tgt_full"] += 1
                else:
                    self.rr[w] += 1
                    if ok:
                        ctr["static_push"] += 1
                if not ok:
                    imm.append((w, task))
            self.creator[task] = w
            if top[1] == 1:
                self.stack[w].pop()
            else:
                top[0] += 1
                top[1] -= 1
        if self.pays_count:
            self.contended_atomic(pushers)
        # NA-RP: an adopted thief is dropped once its quota is used up or
        # its queue was full
        for w in range(n):
            left = self.rp_left[w] - rp_moved[w]
            if rp_failed[w] or left <= 0:
                self.rp_tgt[w], self.rp_left[w] = -1, 0
            else:
                self.rp_left[w] = left
        if imm:
            for w, t in imm:
                d = self.dur[t]
                self.clock[w] += d
                ctr["imm_exec"] += 1
                ctr["exec"] += 1
                ctr["self"] += 1
                ctr["busy_ns"] += d
            self.finish(imm)
            if self.pays_count:
                self.contended_atomic([w for w, _ in imm])

    def dequeue(self) -> dict:
        """Workers with empty stacks take one task: off the global queue in
        lock order, or from their own XQueues, master queue first, then
        the other producers' queues from the rotating start."""
        c, n = self.c, self.n
        found = {}
        idle = [w for w in range(n) if not self.stack[w]]
        if self.locked:
            for rank, w in enumerate(idle):
                self.clock[w] += (c["c_atomic"] + c["c_pq_op"]
                                  + rank * c["c_lock"])
                self.ctr["atomic_ops"] += 1
                if self.gq:
                    found[w] = self.gq.popleft()
            return found
        for w in idle:
            row = self.xq[w]
            src, checked = None, n
            if row[w]:
                src, checked = w, 1
            else:
                rot = self.deq_rr[w]
                for j in range(n - 1):
                    p = (w + 1 + (rot + j) % (n - 1)) % n
                    if row[p]:
                        src, checked = p, j + 2
                        break
            self.clock[w] += checked * c["c_cache"]
            if src is not None:
                found[w] = row[src].popleft()
                self.xq_tasks -= 1
                self.clock[w] += self.m.comm(w, src)
                if src != w:
                    self.deq_rr[w] += 1
        return found

    def thief(self, found: dict) -> None:
        """Idle workers that found nothing send steal requests to
        ``n_victim`` random victims, on their first idle step and every
        ``t_interval`` after.  A request is written only over a stale one.
        Every worker's random stream advances with each round of
        requests, whether it sends or not."""
        n = self.n
        ask = [False] * n
        for w in range(n):
            if not self.stack[w] and w not in found:
                self.idle[w] += 1
                ask[w] = (self.idle[w] == 1
                          or self.idle[w] >= self.t_interval)
                if self.idle[w] >= self.t_interval:
                    self.idle[w] = 0
            else:
                self.idle[w] = 0
        if not any(ask):
            return
        for _ in range(min(self.n_victim, NV_CAP)):
            writes = []
            for w in range(n):
                s = xorshift(self.rng[w])
                want_local = (s >> 8) / float(1 << 24) < self.p_local
                s = xorshift(s)
                self.rng[w] = s
                if not ask[w]:
                    continue
                v = self.m.pick_victim(w, want_local, s >> 1)
                c1 = self.m.comm(w, v)
                self.clock[w] += 2 * c1
                if self.req_round[v] < self.round[v]:
                    self.clock[w] += c1
                    self.ctr["req_sent"] += 1
                    writes.append((v, w))
            for v, w in writes:
                self.req_round[v] = self.round[v]
                self.req_tid[v] = w

    def victim(self, found: dict) -> None:
        """Workers that found a task answer a valid request: NA-WS moves up
        to ``n_steal`` of its queued tasks, in its dequeue order, into the
        thief's queue; NA-RP adopts the thief for later pushes."""
        n, ctr, Q = self.n, self.ctr, self.Q
        victims = [w for w in sorted(found)
                   if self.req_round[w] == self.round[w]]
        moves = []
        for w in victims:
            thief = max(self.req_tid[w], 0)
            if self.naws:
                # decided on the queues as the phase began
                free = Q - len(self.xq[thief][w])
                rot = self.deq_rr[w]
                order = [w] + [(w + 1 + (rot + j) % (n - 1)) % n
                               for j in range(n - 1)]
                left = [len(self.xq[w][p]) for p in order]
                srcs = []
                flag = None
                qi = 0
                while len(srcs) < min(self.n_steal, WS_CAP):
                    if len(srcs) == free:
                        flag = "tgt_full"
                        break
                    while qi < len(order) and left[qi] == 0:
                        qi += 1
                    if qi == len(order):
                        flag = "src_empty"
                        break
                    left[qi] -= 1
                    srcs.append(order[qi])
                moves.append((w, thief, srcs))
                k = len(srcs)
                ctr["stolen"] += k
                ctr["stolen_local" if self.m.same_domain(w, thief)
                    else "stolen_remote"] += k
                ctr["req_has_steal"] += k > 0
                if flag:
                    ctr[flag] += 1
            elif self.rp_tgt[w] < 0:
                self.rp_tgt[w] = thief
                self.rp_left[w] = self.n_steal
                ctr["req_has_steal"] += 1
            self.round[w] += 1
            ctr["req_handled"] += 1
        for w, thief, srcs in moves:
            cost = self.m.comm(w, thief)
            clk = self.clock[w]
            dst = self.xq[thief][w]
            for r, p in enumerate(srcs):
                task, ts = self.xq[w][p].popleft()
                dst.append((task, max(clk + r * cost, ts) + cost))
            self.clock[w] = clk + len(srcs) * cost

    def execute(self, found: dict) -> None:
        """Workers that took a task run it after its push time; a
        memory-bound task runs slower away from its creator's socket."""
        ctr = self.ctr
        items = []
        for w in sorted(found):
            task, ts = found[w]
            cr = self.creator[task]
            d = self.exec_ns(task, cr, w)
            self.clock[w] = max(self.clock[w], ts) + d
            ctr["exec"] += 1
            if cr == w:
                ctr["self"] += 1
            elif self.m.same_domain(cr, w):
                ctr["local"] += 1
            else:
                ctr["remote"] += 1
            ctr["busy_ns"] += d
            items.append((w, task))
        self.finish(items)
        if self.pays_count:
            self.contended_atomic([w for w, _ in items])
        if self.locked and self.central:
            ctr["atomic_ops"] += len(items)


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def simulate(graph, case: dict, sim: dict, machine_cfg: dict | None,
             costs: dict, exec_float: str = "float32") -> dict:
    """One row of the reference.

    ``graph`` has the arrays of :class:`bench.graphs.Graph`; ``case`` names
    ``queue``, ``barrier``, ``balance``, ``n_workers``, ``seed`` and the DLB
    knobs; ``sim`` holds ``queue_cap``, ``stack_cap``, ``max_steps`` (and
    ``n_zones`` for a flat machine); ``costs`` the cost model's constants.
    ``exec_float="bfloat16"`` is the lower-precision control.
    """
    assert case["queue"] in QUEUES and case["barrier"] in BARRIERS \
        and case["balance"] in BALANCERS, case
    sim_case = Case(graph, case, sim, machine_cfg, costs, exec_float)
    while sim_case.running():
        sim_case.step()
    ep_t, ep_a = barrier_episode(case["barrier"], sim_case.n, machine_cfg,
                                 costs)
    counters = dict(sim_case.ctr)
    counters["atomic_ops"] += ep_a
    row = dict(time_ns=max(sim_case.clock) + ep_t, steps=sim_case.steps,
               completed=(sim_case.n_done == sim_case.T
                          and not sim_case.overflow),
               counters=counters)
    row.update(slo(sim_case.done_ns, sim_case.T))
    return row
