"""Cluster tier: (node × socket × core) machines with per-link bandwidth.

Covers the new physics end to end — ``L + payload/B`` link pricing, the
shared inter-node bottleneck occupancy, the two-level victim stratification
(``p_local_node``), the node-tier barrier merge — and, just as load-bearing,
the *absence* contracts: flat and single-node machines are bitwise untouched
(every new charge gates on ``topo.cluster``), ``p_local_node`` is dead (and
key-invisible) off-cluster, payload-free graphs keep their digests, and the
PRNG consumption of ``pick_victim`` never changes (two xorshifts per call).
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import barrier, cache, dlb, taskgraph, topology
from repro.core.costs import DEFAULT_COSTS
from repro.core.scheduler import CTR_NAMES, SimConfig
from repro.core.spec import RuntimeSpec
from repro.core.state import make_case
from repro.core.sweep import CaseSpec, run_cases, run_grid
from repro.core.topology import PRESETS, MachineTopology

from test_phases import check_phases_padded_inert
from test_topology import _assert_bitwise

CFG = SimConfig(n_workers=16, n_zones=4, max_steps=60_000, stack_cap=64)

TWO_NODE = PRESETS["two_node_2x24"]
RACK = PRESETS["rack_4x2x24"]

#: one queue-bound and one memory-bound app, both payload-carrying
GRAPHS = [taskgraph.build("fib", n=9).with_payload(),
          taskgraph.build("sort", levels=5).with_payload()]

SPECS = (RuntimeSpec(balance="na_ws"), RuntimeSpec(balance="na_rp"))


def _cases(specs=SPECS, *, topology=None, p_local=0.5, p_local_node=0.5,
           graphs=GRAPHS):
    return [CaseSpec(spec=sp, n_workers=CFG.n_workers, n_zones=CFG.n_zones,
                     graph=gi, p_local=p_local, t_interval=5,
                     topology=topology, p_local_node=p_local_node)
            for gi in range(len(graphs)) for sp in specs]


# ---------------- host-side model ----------------
def test_cluster_presets_validate():
    for t in (TWO_NODE, RACK):
        assert t.is_cluster
        assert t.n_sockets % t.n_nodes == 0
        assert t.sockets_per_node == t.n_sockets // t.n_nodes
        assert [t.node_of_socket(s) for s in range(t.n_sockets)] \
            == sorted(t.node_of_socket(s) for s in range(t.n_sockets))
        b = np.asarray(t.bandwidth)
        assert (b == b.T).all() and (b > 0).all()
        d = np.asarray(t.dist)
        for i in range(t.n_sockets):
            for j in range(t.n_sockets):
                if t.node_of_socket(i) != t.node_of_socket(j):
                    # cross-node: slower link, higher latency than intra
                    assert d[i][j] > d[i][i] and b[i][j] < b[i][i]
        assert t.bottleneck_bw > 0
    # single-node presets stay out of the cluster tier entirely
    for name in ("uds", "dual_socket_24", "quad_socket_48"):
        t = PRESETS[name]
        assert not t.is_cluster and t.n_nodes == 1
        assert "n_nodes" not in t.asdict()
        assert "n_nodes" not in t.cache_key()


def test_invalid_cluster_topologies_rejected():
    dist, bw = topology._cluster_matrices(2, 2)
    with pytest.raises(AssertionError):    # n_nodes must divide n_sockets
        MachineTopology("bad", 4, 4, dist, n_nodes=3, bandwidth=bw)
    with pytest.raises(AssertionError):    # cluster needs a bandwidth matrix
        MachineTopology("bad", 4, 4, dist, n_nodes=2)
    asym = tuple(tuple(b + (1 if (i, j) == (0, 1) else 0)
                       for j, b in enumerate(row))
                 for i, row in enumerate(bw))
    with pytest.raises(AssertionError):    # bandwidth must be symmetric
        MachineTopology("bad", 4, 4, dist, n_nodes=2, bandwidth=asym)


def test_with_bandwidth_rescales_cross_node_links_only():
    t = TWO_NODE.with_bandwidth(4)
    assert t.name == "two_node_2x24@bw4" and t.is_cluster
    spn = TWO_NODE.sockets_per_node
    for i in range(t.n_sockets):
        for j in range(t.n_sockets):
            if i // spn != j // spn:
                assert t.bandwidth[i][j] == 4, (i, j)
            else:       # intra-node links keep the preset's bandwidth
                assert t.bandwidth[i][j] == TWO_NODE.bandwidth[i][j], (i, j)
    assert t.bottleneck_bw == 4
    assert t.dist == TWO_NODE.dist          # latency matrix untouched
    # distinct machines => distinct cache identity
    assert t.cache_key() != TWO_NODE.cache_key()
    g = taskgraph.build("fib", n=8)
    dg = cache.graph_digest(g)
    assert cache.case_key(dg, CaseSpec(n_workers=8, topology=t), CFG) \
        != cache.case_key(dg, CaseSpec(n_workers=8, topology=TWO_NODE), CFG)


def test_cluster_topo_arrays():
    arrs = RACK.arrays()
    assert bool(arrs.cluster) and not bool(arrs.flat)
    assert list(np.asarray(arrs.node)[:RACK.n_sockets]) \
        == [0, 0, 1, 1, 2, 2, 3, 3]
    assert int(arrs.bneck_bw) == RACK.bottleneck_bw
    bw = np.asarray(arrs.bw)[:RACK.n_sockets, :RACK.n_sockets]
    assert (bw == np.asarray(RACK.bandwidth)).all()
    # single-node machines trace cluster=False and an all-ones bw fill
    dual = PRESETS["dual_socket_24"].arrays()
    assert not bool(dual.cluster)
    assert (np.asarray(dual.bw) == 1).all()


# ---------------- payload graphs & digests ----------------
def test_with_payload_scales_with_mem_bound():
    fib = taskgraph.build("fib", n=9)
    pay = fib.with_payload()
    assert pay.name.startswith(fib.name) and "+pl" in pay.name
    assert pay.payload.shape == (fib.n_tasks,)
    assert (pay.payload >= 0).all()
    pay.validate()
    # memory-bound apps move more bytes per ns of work
    sort = taskgraph.build("sort", levels=5).with_payload()
    assert sort.mem_bound > fib.mem_bound
    assert (sort.payload.mean() / max(float(sort.dur.mean()), 1)
            > pay.payload.mean() / max(float(fib.dur.mean()), 1))


def test_graph_digest_payload_gating():
    base = taskgraph.build("fib", n=9)
    zeros = dataclasses.replace(
        base, payload=np.zeros(base.n_tasks, np.int32))
    loaded = base.with_payload()
    # payload-free and all-zero payloads collapse to the pre-cluster digest
    assert cache.graph_digest(base) == cache.graph_digest(zeros)
    assert cache.graph_digest(loaded) != cache.graph_digest(base)


# ---------------- cache-key warmth ----------------
def test_p_local_node_enters_keys_only_on_clusters():
    g = taskgraph.build("fib", n=8)
    dg = cache.graph_digest(g)

    def key(topo, pn):
        return cache.case_key(dg, CaseSpec(n_workers=8, topology=topo,
                                           p_local_node=pn), CFG)

    # off-cluster (flat and single-node): the knob is dead, keys collapse
    assert key(None, 0.75) == key(None, 0.1)
    assert key("dual_socket_24", 0.75) == key("dual_socket_24", 0.1)
    # on a cluster it steers victim picks, so it must split the key
    assert key("two_node_2x24", 0.75) != key("two_node_2x24", 0.1)


# ---------------- victim selection ----------------
def _lane_state(w_pad):
    me = jnp.arange(w_pad, dtype=jnp.int32)
    rng = me.astype(jnp.uint32) * jnp.uint32(2654435761) + jnp.uint32(7)
    return me, rng


def test_pick_victim_prng_parity_off_cluster():
    """Passing the cluster arguments must not perturb the PRNG stream or
    the picks on non-cluster machines — same two xorshifts, same victims."""
    for preset in ("dual_socket_24", "quad_socket_48"):
        topo = PRESETS[preset].arrays()
        me, rng = _lane_state(16)
        r_legacy, r_new = rng, rng
        for _ in range(50):
            r_legacy, v_legacy = dlb.pick_victim(
                r_legacy, me, 16, 4, jnp.float32(0.5), topo)
            r_new, v_new = dlb.pick_victim(
                r_new, me, 16, 4, jnp.float32(0.5), topo,
                p_local_node=jnp.float32(0.3))
            assert (np.asarray(r_legacy) == np.asarray(r_new)).all(), preset
            assert (np.asarray(v_legacy) == np.asarray(v_new)).all(), preset


def test_pick_victim_two_level_strata():
    """On a cluster, the single uniform stratifies three ways: with
    ``p_local_node=1`` every remote pick stays on the thief's node; with
    ``p_local_node=0`` every remote pick leaves it."""
    topo = TWO_NODE.arrays()
    W, zsz = 16, 4                      # node 0 = workers 0..7
    me, rng0 = _lane_state(W)

    def picks(p_local, p_local_node, rounds=120):
        rng, out = rng0, []
        for _ in range(rounds):
            rng, v = dlb.pick_victim(rng, me, W, zsz,
                                     jnp.float32(p_local), topo,
                                     p_local_node=jnp.float32(p_local_node))
            out.append(np.asarray(v).copy())
        return np.stack(out)            # (rounds, W)

    lanes = np.arange(W)
    node_of = lanes // 8
    v = picks(0.0, 1.0)
    assert (node_of[v] == node_of[lanes][None, :]).all()        # node-local
    assert ((v // zsz) != (lanes // zsz)[None, :]).all()        # yet remote
    v = picks(0.0, 0.0)
    assert (node_of[v] != node_of[lanes][None, :]).all()        # cross-node
    # middle setting reaches both strata
    v = picks(0.0, 0.5)
    same_n = node_of[v] == node_of[lanes][None, :]
    assert same_n.any() and (~same_n).any()


def test_pick_victim_bandwidth_aware_strata():
    """Starving the inter-node fabric narrows the cross-node stratum in
    proportion to the remaining capacity: at ``p_local_node=0`` the native
    fabric sends *every* remote pick cross-node, while ``with_bandwidth(1)``
    (``bw_scale = 1/16``) keeps all but ~1/16 of them on the thief's node."""
    starved_t = TWO_NODE.with_bandwidth(1)
    assert float(TWO_NODE.bw_scale) == 1.0
    assert float(starved_t.bw_scale) == 1.0 / 16.0
    W, zsz = 16, 4
    me, rng0 = _lane_state(W)
    node_of = np.arange(W) // 8

    def xnode_frac(topo, rounds=200):
        rng, cross = rng0, 0
        for _ in range(rounds):
            rng, v = dlb.pick_victim(rng, me, W, zsz, jnp.float32(0.0),
                                     topo, p_local_node=jnp.float32(0.0))
            cross += int((node_of[np.asarray(v)] != node_of).sum())
        return cross / (rounds * W)

    assert xnode_frac(TWO_NODE.arrays()) == 1.0
    f = xnode_frac(starved_t.arrays())
    assert 0.0 < f < 0.2, f             # expect ~1/16 cross-node


# ---------------- victim-weight tables, built once per case ----------------
#: (preset, padded width W, active workers): the flat model, a
#: single-node hierarchy and a cluster with node tables, each at full width
#: and with workers below the padded width
TABLE_CASES = [(None, 16, 16), (None, 16, 11),
               ("quad_socket_48", 48, 48), ("quad_socket_48", 48, 30),
               ("rack_4x2x24", 48, 48), ("rack_4x2x24", 32, 20)]


def _table_case(preset, n_w):
    topo = None if preset is None else PRESETS[preset]
    zsz = max(n_w // 4, 1) if topo is None else topo.zone_size_for(n_w)
    return make_case(RuntimeSpec(balance="na_ws"), n_w, zsz, topology=topo)


@pytest.mark.parametrize("preset,W,n_w", TABLE_CASES,
                         ids=lambda v: str(v))
def test_victim_tables_match_remote_weight_table(preset, W, n_w):
    """The once-per-case tables are bitwise the tables the thief phase
    built at every step: remote, node_local and node_remote pairs."""
    case = _table_case(preset, n_w)
    me = jnp.arange(W, dtype=jnp.int32)
    tb = jax.jit(dlb.victim_tables, static_argnums=0)(W, case)
    want = {
        "remote": dlb.remote_weight_table(me, case.n_workers,
                                          case.zone_size, case.topo),
        **{r: dlb.remote_weight_table(me, case.n_workers, case.zone_size,
                                      case.topo, restrict=r)
           for r in ("node_local", "node_remote")}}
    for name, (cum, total) in want.items():
        got_cum, got_total = getattr(tb, name)
        assert got_cum.shape == (W, W) and got_cum.dtype == jnp.int32
        assert np.array_equal(np.asarray(got_cum), np.asarray(cum)), name
        assert np.array_equal(np.asarray(got_total), np.asarray(total)), name
    if preset == "rack_4x2x24":
        # the cluster's split is real: both node tables carry candidates
        assert (np.asarray(tb.node_local[1])[:n_w] > 0).all()
        assert (np.asarray(tb.node_remote[1])[:n_w] > 0).all()


def test_victim_tables_vmapped_over_mixed_cases():
    """The batched executor builds every lane's tables under one ``vmap``
    over the stacked cases; each lane equals its case built alone."""
    W = 48
    cases = [_table_case(p, n) for p, w, n in TABLE_CASES if w == W]
    cases.append(_table_case(None, 40))
    cb = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *cases)
    tb = jax.jit(jax.vmap(functools.partial(dlb.victim_tables, W)))(cb)
    for i, case in enumerate(cases):
        alone = jax.tree_util.tree_leaves(dlb.victim_tables(W, case))
        lane = jax.tree_util.tree_leaves(tb)
        assert len(lane) == len(alone) == 6
        for x, y in zip(lane, alone):
            assert np.array_equal(np.asarray(x)[i], np.asarray(y)), i


def test_run_grid_rows_bitwise_across_executors_hierarchical():
    """A short grid over the flat, single-node and cluster machines, with
    workers below the padded width, gives the same rows under ``vmap``,
    ``serial`` and ``sharded`` (each builds the tables its own way: per
    lane before the vmapped loop, per case before the serial loop)."""
    g = taskgraph.build("fib", n=8)
    rows = {}
    for strategy in ("vmap", "serial", "sharded"):
        rows[strategy] = run_grid(
            g, balancers=("na_ws", "na_rp"), n_workers=(12, 16),
            p_local=(0.5,), t_interval=(5,), cfg=CFG, strategy=strategy,
            cache=None, topologies=(None, "quad_socket_48", "rack_4x2x24"))
    for strategy in ("serial", "sharded"):
        _assert_bitwise(rows[strategy], rows["vmap"], strategy)


# ---------------- ws_transfer payload pricing ----------------
def test_ws_transfer_zero_payload_matches_constant_cost():
    """The per-task-cost generalization must collapse to the pre-cluster
    closed form when every payload is zero — identical queues, stamps,
    clocks — and report zero moved bytes."""
    from repro.core import xqueue
    W, Q = 4, 8
    xq = xqueue.make(W, Q)
    clock = jnp.arange(W, dtype=jnp.int32) * 10
    # victim 0 holds 5 tasks in its self-queue (only lane 0 pushes)
    victim_mask = jnp.asarray([True, False, False, False])
    lane0 = jnp.zeros(W, jnp.int32)
    for k in range(5):
        xq, ok = xqueue.push(xq, lane0, lane0, jnp.full(W, k, jnp.int32),
                             jnp.full(W, k, jnp.int32), victim_mask)
        assert bool(np.asarray(ok)[0])
    thief = jnp.asarray([2, 0, 0, 0], jnp.int32)
    comm = jnp.full(W, 100, jnp.int32)
    deq_rr = jnp.zeros(W, jnp.int32)
    args = (victim_mask, thief, jnp.int32(3), clock, comm, deq_rr, 8)
    base = dlb.ws_transfer(xq, *args)
    priced = dlb.ws_transfer(xq, *args,
                             payload=jnp.zeros(64, jnp.int32),
                             xfer_bw=jnp.full(W, 16, jnp.int32))
    for a, b, name in zip(base, priced,
                          ("xq", "clock", "k", "src_empty", "tgt_full",
                           "moved")):
        la = jax.tree_util.tree_leaves(a) if name == "xq" else [a]
        lb = jax.tree_util.tree_leaves(b) if name == "xq" else [b]
        for x, y in zip(la, lb):
            assert (np.asarray(x) == np.asarray(y)).all(), name
    assert int(base[5].sum()) == 0 and int(priced[5].sum()) == 0
    # payloads over a finite link pay D/B per task, and the transfer is
    # bounded by the n_steal*L time *window*: at 100 + 160//16 = 110/task
    # only 2 of the 3 requested fit inside 3*100, so the heavy steal moves
    # fewer tasks, each priced dearer
    heavy = dlb.ws_transfer(xq, *args,
                            payload=jnp.full(64, 160, jnp.int32),
                            xfer_bw=jnp.full(W, 16, jnp.int32))
    assert int(heavy[2][0]) == 2
    assert int(heavy[1][0]) == int(clock[0]) + 2 * 110
    assert int(heavy[5][0]) == 2 * 160
    # sub-line payloads (D < B, so D//B == 0) keep the constant-cost
    # arithmetic bitwise yet still attribute their bytes
    light = dlb.ws_transfer(xq, *args,
                            payload=jnp.full(64, 8, jnp.int32),
                            xfer_bw=jnp.full(W, 16, jnp.int32))
    assert int(light[2][0]) == 3
    assert int(light[1][0]) == int(base[1][0])
    assert int(light[5][0]) == 3 * 8


# ---------------- engine: absence contracts ----------------
def test_p_local_node_dead_off_cluster():
    """Varying ``p_local_node`` must be bitwise invisible on flat and
    single-node machines — the knob only exists on clusters."""
    for topo in (None, PRESETS["dual_socket_24"]):
        a = run_cases(GRAPHS, _cases(topology=topo, p_local_node=0.9),
                      cfg=CFG, cache=None)
        b = run_cases(GRAPHS, _cases(topology=topo, p_local_node=0.1),
                      cfg=CFG, cache=None)
        _assert_bitwise(a, b, ("p_local_node-dead", topology.label(topo)))
        assert (a.counters["stolen_xnode"] == 0).all()
        assert (a.counters["xnode_bytes"] == 0).all()


def test_payload_dead_off_cluster():
    """Payload-carrying graphs must price identically to payload-free ones
    everywhere but on cluster machines (the ``D/B`` term gates on
    ``topo.cluster``) — and differently there."""
    bare = [taskgraph.build("fib", n=9), taskgraph.build("sort", levels=5)]
    for topo in (None, PRESETS["quad_socket_48"]):
        a = run_cases(bare, _cases(topology=topo, graphs=bare),
                      cfg=CFG, cache=None)
        b = run_cases(GRAPHS, _cases(topology=topo), cfg=CFG, cache=None)
        _assert_bitwise(a, b, ("payload-dead", topology.label(topo)))
    bare_c = run_cases(bare, _cases(topology=TWO_NODE, graphs=bare),
                       cfg=CFG, cache=None)
    load_c = run_cases(GRAPHS, _cases(topology=TWO_NODE), cfg=CFG,
                       cache=None)
    assert bare_c.completed.all() and load_c.completed.all()
    assert (bare_c.time_ns != load_c.time_ns).any()


def test_flat_rows_bitwise_in_mixed_cluster_batch():
    """Chunks may vmap flat and cluster cases under one compiled step; the
    traced gating must keep the flat rows bitwise identical to a flat-only
    run — the strongest form of the compatibility contract."""
    flat_specs = _cases(topology=None)
    alone = run_cases(GRAPHS, flat_specs, cfg=CFG, cache=None)
    mixed = run_cases(GRAPHS, flat_specs + _cases(topology=TWO_NODE),
                      cfg=CFG, cache=None)
    assert mixed.completed.all()
    n = len(flat_specs)
    assert (mixed.time_ns[:n] == alone.time_ns).all()
    assert (mixed.steps[:n] == alone.steps).all()
    for name in alone.counters:
        assert (mixed.counters[name][:n] == alone.counters[name]).all(), name


# ---------------- engine: cluster physics ----------------
def test_cluster_bitwise_across_executors_and_backends():
    specs = _cases(topology=TWO_NODE)
    ref = None
    for strategy in ("serial", "batched", "sharded"):
        for backend in ("reference", "pallas", "pallas_fused"):
            res = run_cases(GRAPHS, specs, cfg=CFG, strategy=strategy,
                            backend=backend, cache=None)
            assert res.completed.all(), (strategy, backend)
            if ref is None:
                ref = res
                continue
            _assert_bitwise(res, ref, (strategy, backend))


def test_xnode_attribution_counters():
    res = run_cases(GRAPHS, _cases(topology=RACK, p_local=0.25,
                                   p_local_node=0.25), cfg=CFG, cache=None)
    assert res.completed.all()
    st, sx = res.counters["stolen"], res.counters["stolen_xnode"]
    assert (sx <= res.counters["stolen_remote"]).all()
    assert (res.counters["stolen_remote"] <= st).all()
    # with cross-node stealing this likely, traffic must actually cross
    assert sx.sum() > 0
    assert res.counters["xnode_bytes"].sum() > 0


def test_p_local_node_one_confines_stealing_to_nodes():
    """``p_local_node=1`` makes every remote steal request node-local (each
    node has remote-socket candidates at this worker count), so no steal or
    redirect ever crosses a node.  Cross-node *bytes* stay nonzero — spawn
    pushes distribute round-robin over all workers by design — which is
    exactly why ``stolen_xnode`` exists as a separate attribution."""
    res = run_cases(GRAPHS, _cases(topology=TWO_NODE, p_local=0.25,
                                   p_local_node=1.0), cfg=CFG, cache=None)
    assert res.completed.all()
    assert res.counters["stolen"].sum() > 0          # stealing did happen
    assert (res.counters["stolen_xnode"] == 0).all()
    assert res.counters["xnode_bytes"].sum() > 0     # spawn fan-out remains


def test_steal_locality_rises_with_p_local_node():
    """The knob's purpose: raising ``p_local_node`` lowers the fraction of
    steals that cross nodes."""
    def xfrac(pn):
        res = run_cases(GRAPHS, _cases(topology=RACK, p_local=0.25,
                                       p_local_node=pn), cfg=CFG, cache=None)
        assert res.completed.all()
        return (res.counters["stolen_xnode"].sum()
                / max(int(res.counters["stolen"].sum()), 1))

    lo, hi = xfrac(0.05), xfrac(0.95)
    assert lo > hi, (lo, hi)


def test_bandwidth_starvation_slows_cluster():
    """Shrinking the inter-node fabric must never speed a case up once the
    victim policy is held fixed: ``p_local_node=1`` pins the strata to
    node-local whatever ``bw_scale`` is, so the scheduling trace is
    bitwise identical and every cross-node byte (the spawn round-robin's
    fan-out) just costs more.  (With the policy *free* a starved run may
    legitimately beat the native one — it steals node-local instead; see
    test_xnode_steal_fraction_falls_with_bandwidth.)"""
    fast = run_cases(GRAPHS, _cases(topology=TWO_NODE, p_local=0.25,
                                    p_local_node=1.0), cfg=CFG, cache=None)
    slow = run_cases(GRAPHS,
                     _cases(topology=TWO_NODE.with_bandwidth(1),
                            p_local=0.25, p_local_node=1.0),
                     cfg=CFG, cache=None)
    assert fast.completed.all() and slow.completed.all()
    # pinned policy => identical trace: every counter matches, bytes and all
    for name in CTR_NAMES:
        assert (fast.counters[name] == slow.counters[name]).all(), name
    moved = fast.counters["xnode_bytes"] > 0
    assert moved.any()
    assert (slow.time_ns >= fast.time_ns).all()
    assert (slow.time_ns[moved] > fast.time_ns[moved]).all()


def test_xnode_steal_fraction_falls_with_bandwidth():
    """The cluster policy end to end: a starved fabric makes cross-node
    steals *rarer* (the bandwidth-aware strata) and *smaller* (the
    ``n_steal * L`` transfer window prices each task at ``L + D/B``), so
    the cross-node share of stolen tasks falls as bandwidth shrinks."""
    def xfrac(topo):
        res = run_cases(GRAPHS, _cases(topology=topo, p_local=0.25,
                                       p_local_node=0.5),
                        cfg=CFG, cache=None)
        assert res.completed.all()
        return (res.counters["stolen_xnode"].sum()
                / max(int(res.counters["stolen"].sum()), 1))

    fractions = [xfrac(t) for t in
                 (TWO_NODE, TWO_NODE.with_bandwidth(8),
                  TWO_NODE.with_bandwidth(1))]
    assert fractions[0] > fractions[1] > fractions[2], fractions


def test_run_grid_bandwidth_axis():
    res = run_grid(GRAPHS[0], balancers=("na_ws",),
                   topologies=("two_node_2x24",), bandwidths=(None, 8),
                   p_local_node=(0.5,), n_workers=(CFG.n_workers,),
                   cfg=CFG, cache=None)
    assert res.completed.all()
    assert res.grid_axes["bandwidth"] == ("native", 8)
    assert res.grid_axes["p_local_node"] == (0.5,)
    labels = {r["topology"] for r in map(res.row, range(len(res.specs)))}
    assert labels == {"two_node_2x24", "two_node_2x24@bw8"}
    with pytest.raises(AssertionError):   # flat machines have no fabric
        run_grid(GRAPHS[0], topologies=(None,), bandwidths=(8,), cfg=CFG)


# ---------------- barrier node tier ----------------
def test_tree_barrier_node_tier():
    """Same socket count, same W: the cluster machine's top-of-tree merges
    price at the cross-node distance, so its episode strictly exceeds the
    single-node quad socket's — while the atomic count stays W - 1."""
    w = 16
    quad = barrier.tree_episode_topo(w, PRESETS["quad_socket_48"],
                                     DEFAULT_COSTS)
    two = barrier.tree_episode_topo(w, TWO_NODE, DEFAULT_COSTS)
    rack = barrier.tree_episode_topo(w, RACK, DEFAULT_COSTS)
    assert int(quad.time_ns) < int(two.time_ns) <= int(rack.time_ns)
    assert int(two.atomic_ops) == int(rack.atomic_ops) == w - 1


# ---------------- padded-lane inertness ----------------
@pytest.mark.parametrize("spec,preset,n_w,seed,k", [
    (RuntimeSpec(balance="na_ws"), "two_node_2x24", 6, 0, 9),
    (RuntimeSpec(balance="na_rp"), "rack_4x2x24", 7, 1, 9),
], ids=("ws-two-node", "rp-rack"))
def test_padded_lanes_inert_cluster(spec, preset, n_w, seed, k):
    check_phases_padded_inert(spec, n_w, seed, k, topology=PRESETS[preset])
