"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The main path's Pallas kernels are compiled (not run) for one device of a
described ``v5e:2x2`` topology at deployment widths. Interpret mode cannot
tell whether the chip's compiler accepts a block shape or an SMEM load;
these compiles can. The topology is described inside a fixture, never at
import: only one process at a time may load the TPU compiler's library, and
every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dbs
from repro.kernels.dbs import (dbs_copy_pool, dbs_rw_read_pool,
                               dbs_rw_write_pool)
from repro.kernels.paged_attention.kernel import paged_attention_pool_fwd

# the chip_smoke.py geometry: 4 KiB blocks as float32 lanes, 32-block
# extents, 4096 extents plus the reserved dump row, 64-lane batches
E, PAGE, D, B = 4096 + 1, 32, 4096, 64
POOL_BYTES = E * PAGE * D * 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _write_ops(sharding):
    i32 = lambda: _shape(sharding, (B,), jnp.int32)     # noqa: E731
    return dbs.WriteOps(dst=i32(), cow_src=i32(),
                        ok=_shape(sharding, (B,), jnp.bool_))


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # the kernel is there
    return compiled


def test_dbs_rw_write_compiles_in_place(one_chip):
    """The whole write plane (routing + kernel) at the smoke geometry, with
    the pool donated as the engine step donates it: no pool-sized copy."""
    c = _compile(
        lambda pool, ops, pay, blk: dbs_rw_write_pool(pool, ops, pay, blk,
                                                      interpret=False),
        _shape(one_chip, (E, PAGE, D)), _write_ops(one_chip),
        _shape(one_chip, (B, D)), _shape(one_chip, (B,), jnp.int32),
        donate=(0,))
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= POOL_BYTES
    assert mem.temp_size_in_bytes < POOL_BYTES // 64


def test_dbs_rw_read_compiles(one_chip):
    c = _compile(
        lambda pool, ext, blk: dbs_rw_read_pool(pool, ext, blk,
                                                interpret=False),
        _shape(one_chip, (E, PAGE, D)), _shape(one_chip, (B,), jnp.int32),
        _shape(one_chip, (B,), jnp.int32))
    mem = c.memory_analysis()
    assert mem.output_size_in_bytes == B * D * 4
    assert mem.temp_size_in_bytes < POOL_BYTES // 64


def test_dbs_copy_compiles(one_chip):
    _compile(
        lambda pool, src, dst, m: dbs_copy_pool(pool, src, dst, m,
                                                interpret=False,
                                                scratch=True),
        _shape(one_chip, (E, PAGE, D)), _shape(one_chip, (B,), jnp.int32),
        _shape(one_chip, (B,), jnp.int32),
        _shape(one_chip, (B,), jnp.bool_), donate=(0,))


def test_paged_attention_pool_compiles_granite_widths(one_chip):
    """Zero-copy decode attention over one engine pool at granite-3-8b
    widths: 40 layers -> 80 K/V planes, 8 KV heads, head_dim 128, 32 query
    heads; 16-block pages, 32 pages (512 tokens) per sequence."""
    layers, kv, hd, heads = 40, 8, 128, 32
    page, n_pages, seqs, ext = 16, 32, 8, 257
    _compile(
        lambda q, pool, tbl, ln: paged_attention_pool_fwd(
            q, pool, tbl, ln, k_plane=2 * (layers - 1),
            v_plane=2 * layers - 1, interpret=False),
        _shape(one_chip, (seqs, heads, hd)),
        _shape(one_chip, (ext, page, 2 * layers, kv, hd)),
        _shape(one_chip, (seqs, n_pages), jnp.int32),
        _shape(one_chip, (seqs,), jnp.int32))


def test_dbs_rw_kernels_carry_their_names(one_chip):
    """Each kernel's custom call is named, so a chip trace and the compile
    events can tell it from the others."""
    write = jax.jit(
        lambda pool, ops, pay, blk: dbs_rw_write_pool(pool, ops, pay, blk,
                                                      interpret=False)
    ).lower(_shape(one_chip, (E, PAGE, D)), _write_ops(one_chip),
            _shape(one_chip, (B, D)), _shape(one_chip, (B,), jnp.int32))
    read = jax.jit(
        lambda pool, ext, blk: dbs_rw_read_pool(pool, ext, blk,
                                                interpret=False)
    ).lower(_shape(one_chip, (E, PAGE, D)), _shape(one_chip, (B,), jnp.int32),
            _shape(one_chip, (B,), jnp.int32))
    assert 'kernel_name = "dbs_rw_write"' in write.as_text()
    assert 'kernel_name = "dbs_rw_read"' in read.as_text()


def test_ring_step_programs_are_named_by_their_tier(one_chip, monkeypatch):
    """The ring step of a small manager, lowered for the chip with the
    compiled kernels: each program's module is named by its tier, and
    carries the kernels that tier runs by name."""
    from repro.core.blockdev import VolumeManager
    from repro.kernels.dbs import ops

    mgr = VolumeManager(backend="ring", n_replicas=3, payload_elems=128,
                        page_blocks=8, n_extents=16, max_pages=4, batch=8,
                        kernel="pallas")
    impl = mgr.engine.impl
    get_step = impl._get_step
    shapes = {}

    def spy(classes):
        fn, key = get_step(classes)

        def record(*args):
            shapes[key] = jax.tree.map(
                lambda x: _shape(one_chip, x.shape, x.dtype), args)
            return fn(*args)
        return record, key

    monkeypatch.setattr(impl, "_get_step", spy)
    vol = mgr.create()
    vol.write(0, bytes(range(128)))
    assert vol.read(0, 128) == bytes(range(128))
    monkeypatch.setattr(impl, "_get_step", get_step)
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    impl._steps.clear()
    assert set(shapes) == {("read", "write"), ("read",)}
    for key, args in shapes.items():
        fn, _ = impl._get_step(set(key))
        text = fn.lower(*args).as_text()
        assert "module @jit_ring_step_" + "_".join(key) in text
        assert ('kernel_name = "dbs_rw_write"' in text) == ("write" in key)
        assert 'kernel_name = "dbs_rw_read"' in text


@pytest.fixture(scope="module")
def three_chips():
    """A one-axis ("node") mesh over three chips of a described v5e:2x2."""
    import numpy as np
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield jax.sharding.Mesh(np.array(topo.devices[:3]), ("node",))


def test_ring_step_compiles_over_three_chips(three_chips, monkeypatch):
    """The ring step with each replica on its own chip (replica_chips=3),
    compiled for three chips of a v5e:2x2: the uploaded SQE reaches the
    other nodes through collective permutes, the acknowledgement and the
    read return are all-reduces, the kernels are there, and each chip's
    pool is updated in place. The step's arguments are the one-chip
    layout's, restacked as the replica mesh holds them."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.blockdev import VolumeManager
    from repro.kernels.dbs import ops

    mgr = VolumeManager(backend="ring", n_replicas=3, payload_elems=128,
                        page_blocks=8, n_extents=16, max_pages=4, batch=8,
                        kernel="pallas")
    impl = mgr.engine.impl
    get_step = impl._get_step
    captured = {}

    def spy(classes):
        fn, key = get_step(classes)

        def record(*args):
            captured[key] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            return fn(*args)
        return record, key

    monkeypatch.setattr(impl, "_get_step", spy)
    vol = mgr.create()
    vol.write(0, bytes(range(128)))
    assert vol.read(0, 128) == bytes(range(128))
    monkeypatch.setattr(impl, "_get_step", get_step)
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    monkeypatch.setattr(impl, "mesh", three_chips)
    impl._steps.clear()
    node = NamedSharding(three_chips, P("node"))
    shared = NamedSharding(three_chips, P())

    def per_node(x, n=3):
        return jax.ShapeDtypeStruct((n * x.shape[0],) + x.shape[1:],
                                    x.dtype, sharding=node)

    for key in (("read", "write"), ("read",)):
        table, cq, states, pools, page_revs, packed, rr, healthy = \
            captured[key]
        args = (jax.tree.map(per_node, table), jax.tree.map(per_node, cq),
                jax.tree.map(per_node, states[0]),
                per_node(pools[0]), per_node(page_revs[0]), per_node(packed),
                jax.ShapeDtypeStruct(rr.shape, rr.dtype, sharding=shared),
                jax.ShapeDtypeStruct(healthy.shape, healthy.dtype,
                                     sharding=shared))
        fn, _ = impl._get_step(set(key))
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        assert "module_name=jit_ring_step_" + "_".join(key) in text \
            or "HloModule jit_ring_step_" + "_".join(key) in text
        assert "collective-permute-start" in text
        assert "source_target_pairs={{0,1}}" in text
        assert "source_target_pairs={{0,2}}" in text
        assert "all-reduce" in text
        assert "tpu_custom_call" in text
        mem = compiled.memory_analysis()
        if key == ("read", "write"):
            assert mem.alias_size_in_bytes >= pools[0].size * 4
