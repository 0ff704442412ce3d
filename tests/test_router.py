"""DShard router unit tests (ISSUE 8 satellite).

Covers the four behaviours the issue names explicitly — routing-table
construction from the partitioner's placement, stale-table refresh after a
coordinator sync, the misroute fallback (exactly one extra hop, recorded
*and* flagged by the trace checker), and per-shard eviction isolation —
plus the tiered transport's pricing counters and DPlan capacity presizing.
"""

import pytest
from strategies import external_inputs, random_workflow

from repro.core.check import TraceChecker, TraceRecorder
from repro.core.dstore import DStore, GetTimeout
from repro.core.obs import Tracer
from repro.core.partition import partition_workflow, stage_node
from repro.core.plan import build_plan
from repro.core.router import (TIER_IPC, TIER_MEM, TIER_NET, Coordinator,
                               RoutingTable, ShardedDStore, TieredTransport,
                               routes_from_plan, static_routes)

NODES = ["n1", "n2", "n3"]


# ----------------------------------------------------------------------
# Routing-table construction from placement
# ----------------------------------------------------------------------

def test_static_routes_follow_placement():
    wf = random_workflow(11)
    placement = partition_workflow(wf, NODES)
    routes = static_routes(wf, placement, nodes=NODES)
    for f in wf.functions.values():
        for k in f.outputs:
            assert routes[k] == placement[f.name], k
    for k in wf.external_inputs:
        assert routes[k] == stage_node(wf, k, placement, NODES[0]), k


def test_routes_from_plan_agree_with_static():
    """DPlan's transfer matrix names the same homes the placement does —
    the plan is just the richer source (it also sizes per-node peaks)."""
    wf = random_workflow(23)
    placement = partition_workflow(wf, NODES)
    plan = build_plan(wf, placement)
    from_plan = routes_from_plan(plan)
    static = static_routes(wf, placement, nodes=NODES)
    for k, home in from_plan.items():
        assert static[k] == home, k


def test_register_instance_installs_prefixed_routes():
    wf = random_workflow(7)
    placement = partition_workflow(wf, NODES)
    store = ShardedDStore(NODES)
    store.register_instance("fuzz7#0:", wf, placement,
                            plan=build_plan(wf, placement))
    for f in wf.functions.values():
        for k in f.outputs:
            assert store.coordinator.route_of(
                "fuzz7#0:" + k) == placement[f.name]
    # Registration feeds the coordinator only — tables refresh lazily.
    assert all(len(t) == 0 for t in store.tables.values())


def test_presize_from_plan_takes_max_per_node():
    wf = random_workflow(7)
    plan = build_plan(wf, nodes=NODES)
    store = ShardedDStore(NODES)
    store.presize_from_plan(plan)
    for node, peak in plan.peak_resident.items():
        assert store.capacity_bytes[node] == int(peak)
    before = dict(store.capacity_bytes)
    store.presize_from_plan(build_plan(random_workflow(3), nodes=NODES))
    assert all(store.capacity_bytes[n] >= before[n] for n in NODES)


# ----------------------------------------------------------------------
# Stale-table refresh via coordinator sync
# ----------------------------------------------------------------------

def test_stale_table_refresh_after_sync():
    coord = Coordinator(NODES)
    table = RoutingTable("n2")
    assert table.version < coord.version and table.lookup("k") is None
    assert table.misses == 1

    coord.install({"k": "n1"})
    v1 = coord.version
    coord.sync(table)
    assert table.version == v1 and table.refreshes == 1
    assert table.lookup("k") == "n1" and table.hits == 1

    coord.install({"k2": "n3"})          # table is stale again
    assert table.version < coord.version
    assert table.lookup("k2") is None    # stale: doesn't know k2 yet
    coord.sync(table)
    assert table.version == coord.version and table.refreshes == 2
    assert table.lookup("k2") == "n3"
    assert coord.syncs == 2


def test_chunk_keys_route_via_base_key():
    from repro.core.stream import chunk_key

    coord = Coordinator(NODES)
    coord.install({"s": "n3"})
    table = RoutingTable("n1")
    coord.sync(table)
    assert table.lookup(chunk_key("s", 4)) == "n3"
    assert coord.route_of(chunk_key("s", 0)) == "n3"


def test_table_miss_resolves_in_one_hop():
    """First Get on a never-synced table: miss → one coordinator sync →
    the *correct* home shard.  That's the legal refresh path and still
    counts as a 1-hop resolution."""
    store = ShardedDStore(NODES)
    rec = TraceRecorder()
    store.attach_spans(Tracer(recorder=rec))
    store.put("n1", "k", b"v" * 100)          # dynamic home: n1
    assert store.get("n2", "k", timeout=5.0) == b"v" * 100
    assert store.hop_hist[1] == 1 and store.hop_hist[2] == 0
    assert store.tier_gets[TIER_NET] == 1
    assert store.tables["n2"].refreshes == 1
    TraceChecker().check_or_raise(rec.events())
    route = [e for e in rec.events() if e.kind == "route"]
    assert len(route) == 1 and route[0].hops == 1 and route[0].src == "n1"


# ----------------------------------------------------------------------
# Misroute fallback: one extra hop, recorded AND flagged
# ----------------------------------------------------------------------

@pytest.mark.notracecheck
def test_misroute_costs_one_extra_hop_and_is_flagged():
    store = ShardedDStore(NODES)
    rec = TraceRecorder()
    store.attach_spans(Tracer(recorder=rec))
    store.put("n1", "k", b"payload")          # home: n1
    # Poison the consumer's table: stale route pointing at an ALIVE shard
    # that is not the key's home.
    store.tables["n3"].install({"k": "n2"}, version=999)

    assert store.get("n3", "k", timeout=5.0) == b"payload"

    # The fallback: one wasted shard contact (n2), then the authoritative
    # route — 2 hops total, recorded in the histogram and the trace.
    assert store.hop_hist[2] == 1 and store.hop_hist[1] == 0
    route = [e for e in rec.events() if e.kind == "route"]
    assert len(route) == 1 and route[0].hops == 2
    # And the trace checker flags it as a routing-invariant violation.
    violations = TraceChecker().check(rec.events())
    assert any(v.invariant == "routing" for v in violations), violations
    # The fallback also re-synced the table, so the NEXT consumer on n3
    # resolves correctly.
    assert store.tables["n3"].peek("k") == "n1"


# ----------------------------------------------------------------------
# Per-shard eviction isolation
# ----------------------------------------------------------------------

def test_evict_instance_cannot_touch_other_shards_keys():
    wf = random_workflow(5)
    placement = partition_workflow(wf, NODES)
    store = ShardedDStore(NODES)
    for prefix in ("a#0:", "b#0:"):
        store.register_instance(prefix, wf, placement)
        for k, v in external_inputs(wf).items():
            home = stage_node(wf, k, placement, NODES[0])
            store.put(home, prefix + k, v)
        for f in wf.functions.values():
            for k in f.outputs:
                store.put(placement[f.name], prefix + k, b"out:" + k.encode())

    b_keys_before = sorted(k for k in store.directory.keys()
                           if k.startswith("b#0:"))
    b_shard_records = {n: sorted(k for k in sh.keys()
                                 if k.startswith("b#0:"))
                       for n, sh in store.shards.items()}
    store.evict_instance("a#0:")

    # a's keys are gone everywhere: shards, stores, coordinator routes.
    assert not any(k.startswith("a#0:") for k in store.directory.keys())
    assert all(not s.has("a#0:o0") for s in store.stores.values())
    assert store.coordinator.route_of("a#0:o0") is None
    # b's records are untouched on EVERY shard, and its bytes still serve.
    assert sorted(k for k in store.directory.keys()
                  if k.startswith("b#0:")) == b_keys_before
    for n, sh in store.shards.items():
        assert sorted(k for k in sh.keys()
                      if k.startswith("b#0:")) == b_shard_records[n], n
    assert store.get("n1", "b#0:o0", timeout=5.0) == b"out:o0"


def test_routes_survive_key_eviction():
    """Immutability makes a stale route harmless: after evict_key the
    route stays installed and a Get cleanly blocks (no stale bytes)."""
    store = ShardedDStore(NODES)
    store.put("n1", "k", b"v")
    store.evict_key("k")
    assert store.coordinator.route_of("k") == "n1"
    with pytest.raises(GetTimeout):
        store.get("n2", "k", timeout=0.15)


# ----------------------------------------------------------------------
# Tiered transport pricing
# ----------------------------------------------------------------------

def test_tiered_transport_counters():
    t = TieredTransport()
    t.move(100, TIER_NET)
    t.move(50, TIER_MEM)
    t.move(25, TIER_IPC)
    # Base counters keep their single-store (cross-node) meaning.
    assert t.bytes_moved == 100 and t.transfers == 1
    assert t.tier_bytes == {TIER_IPC: 25, TIER_MEM: 50, TIER_NET: 100}
    assert t.tier_transfers == {TIER_IPC: 1, TIER_MEM: 1, TIER_NET: 1}


def test_sharded_store_prices_cross_node_get_as_net():
    t = TieredTransport()
    store = ShardedDStore(NODES, t)
    store.put("n1", "k", b"x" * 64)
    store.get("n2", "k", timeout=5.0)         # cross-node pull
    store.get("n1", "k", timeout=5.0)         # local hit at the home: ipc
    store.get("n2", "k", timeout=5.0)         # local replica hit: mem
    assert t.tier_bytes[TIER_NET] == 64 and t.bytes_moved == 64
    assert store.tier_gets == {TIER_IPC: 1, TIER_MEM: 1, TIER_NET: 1}
    assert store.hop_hist[0] == 2 and store.hop_hist[1] == 1


def test_plain_transport_only_pays_cross_node():
    """With a plain Transport the sharded store charges only net-tier
    pulls, keeping bytes_moved comparable to the single-store baseline."""
    wf = random_workflow(9)
    ext = external_inputs(wf)
    from repro.core.dscheduler import DFlowEngine

    base_eng = DFlowEngine(n_nodes=2, get_timeout=30.0)
    base_rep = base_eng.run(random_workflow(9), ext)

    shard_eng = DFlowEngine(n_nodes=2, get_timeout=30.0, sharded=True)
    shard_store = ShardedDStore(shard_eng.nodes, shard_eng.transport)
    shard_rep = shard_eng.start(wf, ext, store=shard_store).wait()

    assert {k: bytes(v) for k, v in shard_rep.outputs.items()} == \
           {k: bytes(v) for k, v in base_rep.outputs.items()}
    assert isinstance(shard_eng.transport, type(base_eng.transport))


# ----------------------------------------------------------------------
# Failure re-home: coordinator moves routes, Gets follow
# ----------------------------------------------------------------------

def test_fail_node_migrates_surviving_records_and_rehomes():
    store = ShardedDStore(NODES)
    store.put("n1", "k", b"v" * 32)           # home n1, bytes on n1
    store.get("n2", "k", timeout=5.0)         # replica now also on n2
    store.put("n1", "solo", b"only-here")     # no surviving replica

    lost = store.fail_node("n1")
    assert lost == ["solo"]
    # k survived via its n2 replica: re-homed, still gettable, and the
    # resolution is still 1-hop (failure re-home is not a misroute).
    assert store.coordinator.route_of("k") == "n2"
    assert store.get("n3", "k", timeout=5.0) == b"v" * 32
    assert store.hop_hist[2] == 0
    assert not store.coordinator.is_failed("n1")   # node came back empty
