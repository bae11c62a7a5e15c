"""Checkpoint store: on-disk DBS semantics, crash recovery, replication,
elastic resharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointStore, ReplicatedCheckpoint
from repro.core.dbs_host import DBSHost


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (64, 32)),
            "b": jnp.arange(7, dtype=jnp.float32),
            "nested": {"e": jax.random.normal(k, (16, 8)).astype(jnp.bfloat16)}}


def _assert_tree_eq(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_save_restore_roundtrip(tmp_path):
    st = CheckpointStore(str(tmp_path / "ck.dbs"), capacity_bytes=1 << 24)
    t0 = _tree(0)
    st.save("train", 10, t0)
    step, back = st.restore("train", like=t0)
    assert step == 10
    _assert_tree_eq(t0, back)
    # version history via snapshots
    t1 = _tree(1)
    st.save("train", 20, t1)
    step, back = st.restore("train", like=t0)
    assert step == 20
    _assert_tree_eq(t1, back)
    st.close()


def test_crash_torn_write_recovers_previous_version(tmp_path):
    path = str(tmp_path / "ck.dbs")
    st = CheckpointStore(path, capacity_bytes=1 << 24)
    t0 = _tree(0)
    st.save("train", 10, t0)
    # simulate a torn save: corrupt the live head's header block only
    st.dev.write("train", 0, b"\xff" * 4096)
    st.close()
    st2 = CheckpointStore(path, capacity_bytes=1 << 24)
    step, back = st2.restore("train", like=t0)
    assert step == 10                      # fell back to the frozen snapshot
    _assert_tree_eq(t0, back)
    st2.close()


def test_reopen_rebuilds_tables(tmp_path):
    path = str(tmp_path / "ck.dbs")
    st = CheckpointStore(path, capacity_bytes=1 << 24)
    t0 = _tree(3)
    st.save("train", 5, t0)
    st.close()
    st2 = CheckpointStore(path, capacity_bytes=1 << 24)   # open() path
    step, back = st2.restore("train", like=t0)
    assert step == 5
    _assert_tree_eq(t0, back)
    st2.close()


def test_replicated_write_all_fail_rebuild(tmp_path):
    dirs = [str(tmp_path / d) for d in "abc"]
    for d in dirs:
        os.makedirs(d)
    rc = ReplicatedCheckpoint(dirs, capacity_bytes=1 << 24)
    t0 = _tree(0)
    rc.save("train", 7, t0)
    assert rc.consistent()
    rc.fail(0)
    step, back = rc.restore("train", like=t0)     # survives replica loss
    assert step == 7
    _assert_tree_eq(t0, back)
    rc.rebuild(0)
    assert rc.consistent()
    step, back = rc.stores[0].restore("train", like=t0)
    assert step == 7
    rc.close()


def test_elastic_restore_resharding(tmp_path):
    """Restore onto a different (1-device) mesh sharding — the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    st = CheckpointStore(str(tmp_path / "ck.dbs"), capacity_bytes=1 << 24)
    t0 = _tree(0)
    st.save("train", 3, t0)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), t0)
    step, back = st.restore("train", like=t0, shardings=shardings)
    assert step == 3
    _assert_tree_eq(t0, back)
    for leaf in jax.tree.leaves(back):
        assert isinstance(leaf.sharding, NamedSharding)
    st.close()


def test_dbs_host_cow_and_merge(tmp_path):
    path = str(tmp_path / "dev.img")
    d = DBSHost.create(path, n_extents=64, extent_blocks=8, block_size=512,
                       max_pages=64)
    d.create_volume("v")
    data1 = bytes(np.random.default_rng(0).integers(0, 255, 8 * 512,
                                                    dtype=np.uint8))
    d.write("v", 0, data1)
    d.snapshot("v")
    data2 = bytes(np.random.default_rng(1).integers(0, 255, 512,
                                                    dtype=np.uint8))
    d.write("v", 512, data2)               # CoW within the first extent
    assert d.read("v", 0, 512) == data1[:512]
    assert d.read("v", 512, 512) == data2
    # clone isolation
    d.clone("v", "f")
    d.write("f", 0, data2)
    assert d.read("v", 0, 512) == data1[:512]
    assert d.read("f", 512, 512) == data2
    d.delete_volume("f")
    # merge-delete the frozen middle snapshot
    head = d.volumes["v"]
    mid = d.snapshots[head].parent
    d.delete_snapshot(mid)
    assert d.read("v", 0, 512) == data1[:512]
    assert d.read("v", 512, 512) == data2
    d.close()
