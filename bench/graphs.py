"""The benchmark's task-graph generator: BOTS-analogue DAGs from a seed.

A configuration names its apps (builder, arguments, graph seed) and this
module builds them on the host with numpy.  The harness hands the arrays to
the program as its input graphs, and the reference simulates the same
arrays, so both sides run on data neither of them made.

The builders are the repository's ``fib``, ``sort`` and ``uts`` as of the
benchmark's first version (Barcelona OpenMP Task Suite shapes and task-size
distributions: Duran et al., ICPP 2009), kept here so that a later change
to the program's own builders cannot change the benchmark's inputs.

Graph encoding (int32 arrays of length T, task 0 the root): ``dur`` in
simulator ns; the children of t occupy ids ``[first_child[t],
first_child[t] + n_children[t])``; ``notify[t]`` is the join t decrements
on finish (-1: none); ``join_dep[t]`` is a join's dependency count.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import List, Optional

import numpy as np

CYCLE_NS = 0.5  # 2 GHz machine: 1 cycle = 0.5 ns

#: fraction of task runtime that is main-memory bound (drives the NUMA
#: execution penalty)
MEM_BOUND = {"fib": 0.05, "sort": 0.7, "uts": 0.2}


@dataclasses.dataclass(frozen=True)
class Graph:
    name: str
    dur: np.ndarray
    first_child: np.ndarray
    n_children: np.ndarray
    notify: np.ndarray
    join_dep: np.ndarray
    mem_bound: float = 0.0

    @property
    def n_tasks(self) -> int:
        return int(self.dur.shape[0])

    def fields(self) -> dict:
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}


class _Node:
    __slots__ = ("dur", "children", "notify", "dep", "tid")

    def __init__(self, dur: float, dep: int = 0):
        self.dur = max(1, int(dur))
        self.children: List["_Node"] = []
        self.notify: Optional["_Node"] = None
        self.dep = dep
        self.tid = -1


def _linearize(name: str, root: _Node) -> Graph:
    """Contiguous-children ids (BFS over the spawn forest), joins last."""
    order: List[_Node] = [root]
    root.tid = 0
    next_id = 1
    qi = 0
    while qi < len(order):
        node = order[qi]
        qi += 1
        for ch in node.children:
            ch.tid = next_id
            next_id += 1
            order.append(ch)
    seen = {id(n) for n in order}
    joins: List[_Node] = []
    stack = list(order)
    while stack:
        n = stack.pop()
        j = n.notify
        if j is not None and id(j) not in seen:
            seen.add(id(j))
            j.tid = next_id
            next_id += 1
            joins.append(j)
            stack.append(j)
    T = next_id
    dur = np.zeros(T, np.int32)
    first_child = np.zeros(T, np.int32)
    n_children = np.zeros(T, np.int32)
    notify = np.full(T, -1, np.int32)
    join_dep = np.zeros(T, np.int32)
    for n in order + joins:
        t = n.tid
        dur[t] = n.dur
        n_children[t] = len(n.children)
        first_child[t] = n.children[0].tid if n.children else 0
        notify[t] = n.notify.tid if n.notify is not None else -1
        join_dep[t] = n.dep
    return Graph(name, dur, first_child, n_children, notify, join_dep,
                 mem_bound=MEM_BOUND.get(name.split("(")[0], 0.0))


def _cyc(rng: np.random.Generator, lo: float, hi: float) -> float:
    """Log-uniform draw in cycles, returned in ns."""
    return float(np.exp(rng.uniform(np.log(lo), np.log(hi)))) * CYCLE_NS


def fib(n: int, seed: int) -> Graph:
    """Binary call tree; tasks are 10-80 cycles; long critical path of joins."""
    rng = np.random.default_rng(seed)

    def build(k: int):
        if k < 2:
            leaf = _Node(_cyc(rng, 10, 30))
            return leaf, leaf
        call = _Node(_cyc(rng, 20, 80))
        join = _Node(_cyc(rng, 10, 40), dep=2)
        for kk in (k - 1, k - 2):
            entry, compl_ = build(kk)
            call.children.append(entry)
            compl_.notify = join
        return call, join

    old = sys.getrecursionlimit()
    sys.setrecursionlimit(10000)
    try:
        root, _ = build(n)
    finally:
        sys.setrecursionlimit(old)
    return _linearize(f"fib({n})", root)


def sort(levels: int, seed: int) -> Graph:
    """Merge sort: most work ~1e5 cycles (leaf sorts and big merges)."""
    rng = np.random.default_rng(seed)

    def build(level):
        if level == 0:
            leaf = _Node(_cyc(rng, 5e4, 2e5))
            return leaf, leaf
        call = _Node(_cyc(rng, 40, 120))
        join = _Node((2 ** level) * 90 * CYCLE_NS, dep=2)
        for _ in range(2):
            entry, compl_ = build(level - 1)
            call.children.append(entry)
            compl_.notify = join
        return call, join

    root, _ = build(levels)
    return _linearize(f"sort(2^{levels})", root)


def uts(n_target: int, seed: int, b0: float = 2.0) -> Graph:
    """Unbalanced Tree Search: geometric branching, small constant tasks."""
    rng = np.random.default_rng(seed)
    root = _Node(_cyc(rng, 2e2, 8e2))
    frontier = [root]
    total = 1
    first = True
    while frontier and total < n_target:
        node = frontier.pop(rng.integers(0, len(frontier)))
        nkids = rng.geometric(1.0 / b0) if rng.random() < 0.7 else 0
        if first:   # the root always branches
            nkids = max(nkids, 4)
            first = False
        nkids = int(min(nkids, n_target - total))
        if nkids == 0:
            continue
        # taskwait: the join waits on the direct children
        join = _Node(20 * CYCLE_NS, dep=nkids)
        for _ in range(nkids):
            ch = _Node(_cyc(rng, 2e2, 8e2))
            ch.notify = join
            node.children.append(ch)
            frontier.append(ch)
            total += 1
    return _linearize(f"uts({n_target})", root)


BUILDERS = {"fib": fib, "sort": sort, "uts": uts}


def build(app: dict, seed: int) -> Graph:
    """One configuration app entry: ``{"name", "builder", "args"}``."""
    return BUILDERS[app["builder"]](**app["args"], seed=seed)


def stand_in() -> Graph:
    """A graph of one task: the set-up's warm-up runs the cell's grid on
    it, which compiles every shape the grid uses at almost no run time
    (shapes follow the largest graph of a call, which this never is)."""
    one = np.ones(1, np.int32)
    zero = np.zeros(1, np.int32)
    return Graph("stand-in", dur=one, first_child=zero, n_children=zero,
                 notify=-one, join_dep=zero)
