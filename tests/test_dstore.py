"""Real (threaded) DStore tests: Table 1 API, block/wake, replicas, faults,
and the co-write check that both stores share."""

import threading
import time

import numpy as np
import pytest

from repro.core.check import TraceRecorder, content_digest
from repro.core.dstore import (DStore, GetTimeout, ImmutabilityError,
                               Transport)
from repro.core.obs import MetricsRegistry
from repro.core.router import ShardedDStore


def test_put_get_local():
    ds = DStore(["n0", "n1"])
    ds.put("n0", "k", b"hello")
    assert ds.get("n0", "k") == b"hello"
    assert ds.transport.transfers == 0      # local hit: no network


def test_get_remote_receiver_driven():
    ds = DStore(["n0", "n1"])
    ds.put("n0", "k", b"payload")
    assert ds.get("n1", "k") == b"payload"
    assert ds.transport.transfers == 1
    # After the pull the consumer node holds a replica; next get is local.
    assert ds.get("n1", "k") == b"payload"
    assert ds.transport.transfers == 1


def test_auto_block_wake():
    """Get blocks until the producer publishes (paper §3.3.2)."""
    ds = DStore(["n0", "n1"])
    got = {}

    def consumer():
        got["v"] = ds.get("n1", "late")
    th = threading.Thread(target=consumer)
    th.start()
    time.sleep(0.05)
    assert "v" not in got                    # still blocked
    ds.put("n0", "late", 42)
    th.join(timeout=5)
    assert got["v"] == 42


def test_get_timeout():
    ds = DStore(["n0"])
    with pytest.raises(GetTimeout):
        ds.get("n0", "never", timeout=0.05)


def test_replica_least_access_frequency():
    """With replicas on two nodes, concurrent fetches spread across them."""
    ds = DStore(["n0", "n1", "n2", "n3"])
    ds.put("n0", "k", b"x" * 1000)
    ds.get("n1", "k")                         # replica now on n0 + n1
    # choose_replica alternates by in-flight count.
    first = ds.directory.choose_replica("k")
    second = ds.directory.choose_replica("k")
    assert {first, second} == {"n0", "n1"}
    ds.directory.release_replica("k", first)
    ds.directory.release_replica("k", second)


def test_immutability_first_writer_wins():
    ds = DStore(["n0"])
    ds.put("n0", "k", "first")
    ds.put("n0", "k", "first")                # identical co-write: no-op
    assert ds.get("n0", "k") == "first"


def test_immutability_divergent_cowrite_rejected():
    # A straggler re-execution must produce the same bytes; anything else
    # breaks the determinism premise first-writer-wins rests on — from
    # any node, same or different.
    ds = DStore(["n0", "n1"])
    ds.put("n0", "k", "first")
    with pytest.raises(ImmutabilityError):
        ds.put("n0", "k", "second")
    with pytest.raises(ImmutabilityError):
        ds.put("n1", "k", "second")
    assert ds.get("n1", "k") == "first"


def test_immutability_opaque_cowrite_tolerated():
    # Values with no reliable byte representation can't be compared;
    # the check stays conservative (first-writer-wins, no rejection).
    class Opaque:
        pass

    ds = DStore(["n0"])
    ds.put("n0", "k", Opaque())
    ds.put("n0", "k", Opaque())
    ds.get("n0", "k")


def test_fail_node_drops_replicas():
    ds = DStore(["n0", "n1"])
    ds.put("n0", "only_here", 1)
    ds.put("n0", "replicated", 2)
    ds.get("n1", "replicated")                # replica on n1
    lost = ds.fail_node("n0")
    assert lost == ["only_here"]              # replicated survives on n1
    assert ds.get("n1", "replicated") == 2


def test_transport_accounting():
    tr = Transport()
    ds = DStore(["n0", "n1"], tr)
    import numpy as np
    arr = np.zeros(1024, dtype=np.uint8)
    ds.put("n0", "arr", arr)
    ds.get("n1", "arr")
    assert tr.bytes_moved == 1024


# ----------------------------------------------------------------------
# Co-write check (DStore and ShardedDStore share it)
# ----------------------------------------------------------------------

STORES = pytest.mark.parametrize("make", [DStore, ShardedDStore],
                                 ids=["dstore", "sharded"])


class CountedLeaf:
    """An array leaf that counts its copies to the host (``tobytes``), as
    a device array's would be."""

    def __init__(self, data):
        self.data = np.asarray(data)
        self.dtype, self.shape = self.data.dtype, self.data.shape
        self.nbytes = self.data.nbytes
        self.copies = 0

    def tobytes(self):
        self.copies += 1
        return self.data.tobytes()


def untraced(make):
    store = make(["n0", "n1"])
    store.attach_tracer(None)       # the premise: no DCheck recorder
    return store


@STORES
def test_first_put_never_copies_to_the_host(make):
    store = untraced(make)
    leaf = CountedLeaf(np.arange(8.0))
    value = {"kv": (leaf, 3)}
    store.put("n0", "k", value)
    assert store.get("n0", "k") is value
    assert store.get("n1", "k") is value        # a replica, still uncopied
    assert leaf.copies == 0
    assert store.directory.peek("k").digest is None


@STORES
def test_divergent_cowrite_raises_from_any_node(make):
    store = untraced(make)
    store.put("n0", "k", CountedLeaf(np.arange(4)))
    for node in ("n0", "n1"):
        with pytest.raises(ImmutabilityError):
            store.put(node, "k", CountedLeaf(np.arange(1, 5)))
    assert np.array_equal(store.get("n1", "k").data, np.arange(4))


@STORES
def test_identical_cowrite_is_a_duplicate(make):
    store = untraced(make)
    first = CountedLeaf(np.arange(4))
    store.put("n0", "k", first)
    store.put("n0", "k", CountedLeaf(np.arange(4)))
    store.put("n1", "k", CountedLeaf(np.arange(4)))
    assert store.get("n0", "k") is first         # first-writer-wins
    assert first.copies == 1                     # digested once, then kept
    assert store.directory.peek("k").digest == \
        content_digest(np.arange(4))


@STORES
def test_cowrite_checks_count_exactly_the_cowrites(make):
    store = untraced(make)
    reg = MetricsRegistry()
    store.register_metrics(reg)
    for k in ("a", "b", "c"):
        store.put("n0", k, k.encode())
    store.get("n1", "a")                         # a replica is no co-write
    assert reg.collect()["counters"]["dstore_cowrite_checks"] == 0
    store.put("n0", "a", b"a")
    store.put("n1", "b", b"b")
    with pytest.raises(ImmutabilityError):
        store.put("n1", "c", b"x")
    assert reg.collect()["counters"]["dstore_cowrite_checks"] == 3


@STORES
def test_traced_put_events_carry_the_digest(make):
    store = make(["n0", "n1"])
    rec = TraceRecorder()
    store.attach_tracer(rec)
    leaf = CountedLeaf(np.arange(6.0))
    store.put("n0", "k", {"kv": leaf})
    (put,) = [e for e in rec.events() if e.kind == "put"]
    assert put.digest == content_digest({"kv": np.arange(6.0)})
    assert leaf.copies == 1
    # The co-write compares against the digest DCheck already took.
    store.put("n1", "k", {"kv": CountedLeaf(np.arange(6.0))})
    assert leaf.copies == 1


@STORES
def test_racing_cowrites_count_each_and_digest_the_first_once(make):
    import sys

    store = untraced(make)
    reg = MetricsRegistry()
    store.register_metrics(reg)
    leaves = [CountedLeaf(np.arange(16)) for _ in range(24)]
    errors = []

    def write(i):
        try:
            store.put(("n0", "n1")[i % 2], "k", leaves[i])
        except Exception as exc:         # noqa: BLE001 - asserted below
            errors.append(exc)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write, args=(i,))
                   for i in range(len(leaves))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(10)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(th.is_alive() for th in threads)
    assert reg.collect()["counters"]["dstore_cowrite_checks"] == \
        len(leaves) - 1
    # Each co-writer digests its own value; the first value is digested
    # once, by whichever co-writer came first, and then kept.
    assert [leaf.copies for leaf in leaves] == [1] * len(leaves)
