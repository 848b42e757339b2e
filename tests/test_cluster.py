"""Multi-node cluster: partitioned pull/push, failure, elastic reshard."""

import numpy as np
import pytest

from repro.core.elastic import reshard
from repro.core.node import Cluster, NodeDownError


def make_cluster(tmp_path, n=4, dim=4):
    return Cluster(n, str(tmp_path / f"c{n}"), dim=dim, cache_capacity=512, file_capacity=32)


def test_partitioned_pull_push(tmp_path):
    cl = make_cluster(tmp_path)
    keys = np.random.default_rng(0).integers(0, 2**40, 300).astype(np.uint64)
    v = cl.pull(keys)
    cl.push(keys, v + 2.0)
    np.testing.assert_allclose(cl.pull(np.unique(keys), pin=False),
                               np.unique(keys)[:, None] * 0 + (cl.pull(np.unique(keys), pin=False)))
    got = cl.pull(keys, requester=2, pin=False)
    np.testing.assert_allclose(got, v + 2.0)
    assert cl.network.bytes_moved > 0  # remote traffic happened


def test_remote_vs_local_accounting(tmp_path):
    cl = make_cluster(tmp_path)
    keys = np.arange(1000, dtype=np.uint64)
    cl.pull(keys, requester=0, pin=False)
    # one request and one reply per remote node; the local shard sends none
    assert cl.network.messages == 2 * (cl.n_nodes - 1)
    # ~3/4 of keys are remote for requester 0
    owners = cl.owner_of(keys)
    assert 0.5 < (owners != 0).mean() < 0.95


def test_node_failure_raises_and_restart(tmp_path):
    cl = make_cluster(tmp_path)
    keys = np.arange(100, dtype=np.uint64)
    v = cl.pull(keys, pin=False)
    cl.push(keys, v + 1, unpin=False)
    cl.flush_all()
    cl.kill_node(1)
    with pytest.raises(NodeDownError):
        cl.pull(keys, pin=False)
    cl.nodes[1].restart()
    got = cl.pull(keys, pin=False)  # SSD state survived the DRAM loss
    np.testing.assert_allclose(got, v + 1)


def test_manifest_restore_roundtrip(tmp_path):
    cl = make_cluster(tmp_path)
    keys = np.arange(200, dtype=np.uint64)
    v = cl.pull(keys)
    cl.push(keys, v * 3)
    manifest = cl.manifest()
    cl2 = Cluster.restore(manifest, cl.base_dir)
    np.testing.assert_allclose(cl2.pull(keys, pin=False), v * 3)


def test_elastic_reshard_preserves_ctor_kwargs_and_tables(tmp_path):
    """reshard must rebuild the new cluster from the FULL ctor-kwarg set
    (the hand-picked subset used to silently revert file_capacity/init
    settings to defaults) and carry the hosted table specs — including
    their key namespacing and per-table missing-row init — onto the new
    shards."""
    from repro.core.client import PSClient
    from repro.core.keys import deterministic_init
    from repro.core.node import NetworkModel
    from repro.core.tables import RowSchema, TableRegistry, TableSpec

    spec = TableSpec("t", RowSchema.with_adagrad(3), table_id=4, init_scale=0.3)
    cl = Cluster(3, str(tmp_path / "src"), dim=8, cache_capacity=77,
                 file_capacity=24, init_scale=0.05, init_cols=6,
                 network=NetworkModel(latency_s=3e-4, bandwidth_gbps=9.0,
                                      wire_quantize=True),
                 tables=TableRegistry([spec]))
    client = PSClient(cl)
    raw = np.arange(60, dtype=np.uint64)
    with client.session("t", raw) as s:
        s.commit(np.full((60, 3), 4.0, np.float32), np.full((60, 3), 5.0, np.float32))

    new = reshard(cl, 2, str(tmp_path / "dst"))
    # full kwargs carried (file_capacity/init_* used to fall back to defaults)
    assert new.cache_capacity == 77 and new.file_capacity == 24
    assert new.init_scale == 0.05 and new.init_cols == 6
    assert all(n.ssd.file_capacity == 24 for n in new.nodes)
    assert all(n.mem.capacity == 77 for n in new.nodes)
    # NIC parameters carried, counters fresh for this reshard's traffic
    assert new.network.latency_s == 3e-4 and new.network.bandwidth_gbps == 9.0
    assert new.network.wire_quantize and new.network is not cl.network
    # table specs carried: rows, namespacing and per-table init all intact
    # (pinned pulls: the carried wire_quantize=True makes unpinned remote
    # reads intentionally lossy, training pulls stay exact)
    assert new.tables is not None and new.tables.get("t") == spec
    rows = new.pull(spec.namespace(raw), pin=True)
    new.unpin(spec.namespace(raw))
    np.testing.assert_array_equal(rows[:, :3], np.full((60, 3), 4.0))
    np.testing.assert_array_equal(rows[:, 3:6], np.full((60, 3), 5.0))
    unseen = spec.namespace(np.arange(500, 504, dtype=np.uint64))
    want = deterministic_init(unseen, 3, 0.3)
    got = new.pull(unseen, pin=True)
    new.unpin(unseen)
    np.testing.assert_array_equal(got[:, :3], want)


@pytest.mark.parametrize("new_n", [2, 6])
def test_elastic_reshard_preserves_rows(tmp_path, new_n):
    cl = make_cluster(tmp_path, n=4)
    keys = np.random.default_rng(1).integers(0, 2**40, 500).astype(np.uint64)
    keys = np.unique(keys)
    v = cl.pull(keys)
    cl.push(keys, v + 5)
    new = reshard(cl, new_n, str(tmp_path / f"resharded{new_n}"))
    got = new.pull(keys, pin=False)
    np.testing.assert_allclose(got, v + 5)
    # every node owns roughly 1/new_n of the keys
    counts = np.bincount(new.owner_of(keys), minlength=new_n)
    assert counts.min() > 0.7 * counts.mean()


# ---------------------------------------------- concurrent node segments
# A pull or push of at least CONCURRENT_MIN_KEYS keys runs the nodes'
# segments at once. The tests below drive calls just above that size and
# hold them to a plain serial loop over the nodes, written here.

from dataclasses import asdict, fields  # noqa: E402
import os  # noqa: E402

from repro import tracing  # noqa: E402
from repro.core.faults import NIC_STALL, NODE_KILL, SSD_DROP, FaultInjector, FaultSpec  # noqa: E402
from repro.core.node import CONCURRENT_MIN_KEYS  # noqa: E402

BIG = CONCURRENT_MIN_KEYS + 4096  # keys per call


def big_cluster(tmp_path, tag, **kw):
    kw.setdefault("cache_capacity", 20_000)  # two pinned calls of BIG keys fit
    return Cluster(4, str(tmp_path / tag), dim=4, file_capacity=256, **kw)


def big_batches(n, seed=0):
    """Sorted unique keys, BIG per batch, from a range eight batches wide:
    repeats hit the cache, and every batch from the third evicts."""
    rng = np.random.default_rng(seed)
    return [np.sort(rng.choice(np.arange(1, 8 * BIG, dtype=np.uint64), BIG, replace=False))
            for _ in range(n)]


def owner_segments(cl, keys):
    """(order, bounds): keys[order] owner-sorted, node n's keys in
    bounds[n]:bounds[n + 1]."""
    owners = cl.owner_of(keys)
    order = np.argsort(owners, kind="stable")
    return order, np.searchsorted(owners[order], np.arange(cl.n_nodes + 1))


def serial_pull(cl, keys, pin=True, requester=0):
    """The cluster's pull as a loop over its nodes, one after another; a
    dead node is recovered first when the cluster recovers automatically."""
    order, bounds = owner_segments(cl, keys)
    sk = keys[order]
    out = np.empty((len(keys), cl.dim), dtype=np.float32)
    for n in range(cl.n_nodes):
        lo, hi = bounds[n], bounds[n + 1]
        if lo == hi:
            continue
        if cl.auto_recover and not cl.nodes[n].alive:
            cl.recover_node(n)
        vals = cl.nodes[n].pull(sk[lo:hi], pin=pin)
        if n != requester:
            cl.network.transfer((hi - lo) * 8)
            vals = cl.network.reply(sk[lo:hi], vals, serving=not pin)
        out[lo:hi] = vals
    res = np.empty_like(out)
    res[order] = out
    return res


def serial_push(cl, keys, values, unpin=True, requester=0):
    if cl.redo is not None:
        cl.redo.append(keys, values)
    order, bounds = owner_segments(cl, keys)
    sk, sv = keys[order], values[order]
    for n in range(cl.n_nodes):
        lo, hi = bounds[n], bounds[n + 1]
        if lo == hi:
            continue
        if n != requester:
            cl.network.transfer((hi - lo) * (8 + 4 * cl.dim))
        cl.nodes[n].push(sk[lo:hi], sv[lo:hi], unpin=unpin)


def node_state(node):
    """Everything a node holds: MEM-PS arrays, index, pending buffer and
    stats; SSD-PS index, files (by name), their bytes and stats."""
    m, s = node.mem, node.ssd
    st = {f"mem.{a}": getattr(m, a) for a in (
        "arena", "key_of_row", "freq", "pins", "dirty", "tier", "last_used",
        "lfu_time", "_clock", "_n_lru", "_n_lfu", "_free_n", "_pend_vals", "_pend_free_n")}
    st["mem.free"] = m._free[: m._free_n]
    st["mem.pend_free"] = m._pend_free[: m._pend_free_n]
    for tag, ix in (("mem.index", m.index), ("mem.pend_index", m._pend_index), ("ssd.index", s.index)):
        st.update({f"{tag}.{a}": getattr(ix, a) for a in ("keys", "vals", "state", "n_full", "n_tomb")})
    st["mem.stats"] = asdict(m.stats)
    st["mem.snapshot"] = m.debug_snapshot()
    st["ssd.stats"] = asdict(s.stats)
    st["ssd.next_file_id"] = s._next_file_id
    st["ssd.files"] = {fid: (os.path.basename(f.path), f.n_rows, f.n_stale) for fid, f in s.files.items()}
    for fid, f in s.files.items():
        with open(f.path, "rb") as fh:
            st[f"ssd.file.{fid}"] = fh.read()
    return st


def assert_same_state(a, b):
    for na, nb in zip(a.nodes, b.nodes):
        sa, sb = node_state(na), node_state(nb)
        assert sa.keys() == sb.keys()
        for k in sa:
            if isinstance(sa[k], np.ndarray):
                assert sa[k].dtype == sb[k].dtype and np.array_equal(sa[k], sb[k]), (na.node_id, k)
            else:
                assert sa[k] == sb[k], (na.node_id, k)
    nic = [f.name for f in fields(a.network) if f.name != "faults"]
    assert [getattr(a.network, f) for f in nic] == [getattr(b.network, f) for f in nic]
    assert a.fault_counters.snapshot() == b.fault_counters.snapshot()


def node_spans(name):
    return [s for s in tracing.recorded() if s[0] == "hps:" + name]


@pytest.mark.parametrize("pin", [True, False], ids=["pinned", "unpinned"])
def test_concurrent_segments_match_a_serial_loop(tmp_path, pin):
    """Six batches, each pulled while the previous one is still pinned and
    then pushed, as the pipeline does: rows, every node's MEM-PS and SSD-PS
    state (files included), their stats and the NIC's counters are the
    serial loop's, bit for bit."""
    pool, ref = big_cluster(tmp_path, "pool"), big_cluster(tmp_path, "ref")
    tracing.clear()
    prev = None
    for b, keys in enumerate(big_batches(6)):
        req = b % 4
        got = pool.pull(keys, requester=req, pin=pin)
        np.testing.assert_array_equal(got, serial_pull(ref, keys, pin, req))
        if prev is not None:
            pool.push(*prev, unpin=pin, requester=req)
            serial_push(ref, *prev, unpin=pin, requester=req)
        prev = (keys, got * 0.5 + np.float32(b))
        assert_same_state(pool, ref)
    pool.push(*prev, unpin=pin)
    serial_push(ref, *prev, unpin=pin)
    assert_same_state(pool, ref)
    assert pool.total_pins() == 0
    assert all(n.mem.stats.evict_lfu_to_ssd > 0 and n.ssd.stats.files_read > 0 for n in pool.nodes)
    # the calls above ran their segments on the pool: one span per node
    assert len(node_spans("node.pull")) == 6 * 4 and len(node_spans("node.push")) == 6 * 4
    assert {s[4]["node"] for s in node_spans("node.pull")} == {0, 1, 2, 3}


@pytest.mark.parametrize("failure", ["node_down", "pin_pressure"])
def test_concurrent_pull_failure_rolls_back_every_segment(tmp_path, failure):
    """A segment that fails under the pool (its node killed directly, or
    its MEM-PS out of unpinned rows) fails the pull once every segment has
    finished, and the pins of every segment that ran are rolled back."""
    cl = big_cluster(tmp_path, "c", cache_capacity=12_000)
    keys = big_batches(1)[0]
    held = np.zeros(0, dtype=np.uint64)
    if failure == "node_down":
        cl.nodes[1].kill()
        err = NodeDownError
    else:  # node 2 keeps 11,000 of its 12,000 rows pinned
        cand = np.arange(10 * BIG, 20 * BIG, dtype=np.uint64)
        held = cand[cl.owner_of(cand) == 2][:11_000]
        cl.pull(held)
        err = MemoryError
    tracing.clear()
    with pytest.raises(err):
        cl.pull(keys)
    assert {s[4]["node"] for s in node_spans("node.pull")} == {0, 1, 2, 3}
    assert cl.total_pins() == len(held)
    assert all(n.mem.total_pins == 0 for n in cl.nodes if n.alive and n.node_id != 2)
    cl.unpin(held)


def test_concurrent_pull_recovers_a_dead_node_like_the_serial_loop(tmp_path):
    """With ``auto_recover``, a node killed directly is recovered on the
    calling thread, in node order: the pull succeeds, and rows, node state
    and NIC counters (redo replay included) are the serial loop's."""
    kw = dict(redo_rows=10**9, auto_recover=True)
    pool, ref = big_cluster(tmp_path, "pool", **kw), big_cluster(tmp_path, "ref", **kw)
    k0, k1 = big_batches(2, seed=3)
    v = pool.pull(k0)
    serial_pull(ref, k0)
    pool.push(k0, v + 1)
    serial_push(ref, k0, v + 1)
    pool.kill_node(1)
    ref.kill_node(1)
    tracing.clear()
    got = pool.pull(k1)
    np.testing.assert_array_equal(got, serial_pull(ref, k1))
    assert pool.fault_counters["node_recoveries"] == 1
    assert_same_state(pool, ref)
    assert len(node_spans("node.pull")) == 4
    pool.unpin(k1)
    ref.unpin(k1)


def _injected_run(tmp_path, tag):
    cl = big_cluster(tmp_path, tag, redo_rows=10**9, auto_recover=True)
    pin = cl.pin_redo()  # keep the whole log: dropped files heal from it
    inj = FaultInjector([
        FaultSpec(NODE_KILL, at_op=6, node_id=2),
        FaultSpec(SSD_DROP, at_op=3),
        FaultSpec(NIC_STALL, at_op=5, stall_s=0.01),
    ]).arm(cl)
    tracing.clear()
    rows = []
    for keys in big_batches(4, seed=5):
        v = cl.pull(keys)
        cl.push(keys, v + 1)
        rows.append(v)
    assert not node_spans("node.pull") and not node_spans("node.push")
    assert inj.all_fired()
    log = [{k: os.path.basename(v) if k == "path" else v for k, v in f.items()} for f in inj.fired]
    cl.release_redo(pin)
    return log, rows, cl


def test_armed_injector_runs_segments_serially_and_repeats(tmp_path):
    """While an injector is armed, a pull above the threshold runs its
    segments in node order, so the same schedule fires at the same ops and
    leaves the same rows on every run."""
    log0, rows0, cl0 = _injected_run(tmp_path, "a")
    for tag in ("b", "c"):
        log, rows, cl = _injected_run(tmp_path, tag)
        assert log == log0
        for r, r0 in zip(rows, rows0):
            np.testing.assert_array_equal(r, r0)
        assert_same_state(cl, cl0)
    assert cl0.fault_counters["node_recoveries"] == 1
    assert cl0.fault_counters["ssd_files_quarantined"] == 1


def test_nodes_heal_at_once_exactly_under_a_short_switch_interval(tmp_path):
    """One file lost on each of 16 nodes: one pull quarantines and heals
    them from 16 pool workers (more than the cores), with the interpreter
    switching threads every few microseconds. The heals take turns, the
    shared counters lose no update, and every row is the exact one."""
    import sys
    import threading

    cl = Cluster(16, str(tmp_path / "c"), dim=4, cache_capacity=3_000, file_capacity=256,
                 redo_rows=10**9)
    pin = cl.pin_redo()
    keys = big_batches(1, seed=7)[0]
    rows = np.random.default_rng(8).normal(size=(len(keys), cl.dim)).astype(np.float32)
    cl.push(keys, rows, unpin=False)
    cl.flush_all()
    for node in cl.nodes:  # a cold cache over the same files, one file gone
        node.kill()
        node.restart()
        os.remove(next(iter(node.ssd.files.values())).path)
    tracing.clear()
    got = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t = threading.Thread(target=lambda: got.append(cl.pull(keys, pin=False)))
        t.start()
        t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not t.is_alive() and len(got) == 1
    np.testing.assert_array_equal(got[0], rows)
    assert len(node_spans("node.pull")) == 16
    assert cl.fault_counters["ssd_files_quarantined"] == 16
    assert cl.fault_counters["ssd_rows_healed"] == 16 * 256
    assert cl.fault_counters["ssd_rows_reinit"] == 0
    cl.release_redo(pin)
