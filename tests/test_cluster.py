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
