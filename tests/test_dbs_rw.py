"""The ``dbs_rw`` kernel family + the kernel registry (ISSUE 7).

Four contracts:

1. every REGISTERED kernel's write/read data plane is bit-identical to the
   ``xla`` reference (``apply_write_ops`` + the hole-masked gather) over
   parametrized geometries — multi-block spans, holes/unmapped pages,
   duplicate-dst write groups, failed lanes, scratch-row masking — in
   interpret mode, and under ``vmap`` (the sharded path's form),
2. the registry API mirrors the backend/transport registries
   (register/make/available, ``EngineConfig(kernel=...)`` validation, the
   legacy ``cow`` axis resolution),
3. ``kernel="pallas"`` threads END TO END: byte-oracle equivalence with
   ``kernel="xla"`` through the public ``VolumeManager`` API on the
   fused/sharded/ring backends, and one chaos-harness scenario,
4. ``ops.dbs_copy`` resolves its interpret mode per CALL (the stale
   module-level ``@jax.jit`` capture is fixed).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Engine, EngineConfig, Request, dbs
from repro.core.blockdev import VolumeManager
from repro.kernels.dbs import (available_kernels, dbs_rw_read_pool,
                               dbs_rw_write_pool, make_kernel,
                               register_kernel, resolve_kernel_name)
from repro.kernels.dbs.registry import _REGISTRY, DBSKernel
from repro.kernels.platform import default_interpret

KEY = jax.random.PRNGKey(0)


def _assert_rows_equal(out, ref, *, excl_dump=True):
    e = out.shape[0]
    n = e - 1 if excl_dump else e
    np.testing.assert_array_equal(np.asarray(out[:n]), np.asarray(ref[:n]))


# ---------------------------------------------------------------------------
# 1. bit-equivalence over geometries (every registered kernel vs xla)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("e,page,d,b", [
    (16, 4, 8, 8),       # the crafted-lane geometry
    (33, 8, 16, 12),     # odd extent count, wider rows
    (9, 2, 4, 16),       # more lanes than live extents (heavy grouping)
])
@pytest.mark.parametrize("kernel", ["pallas", "ref", "copy"])
def test_write_matches_xla_crafted(kernel, e, page, d, b):
    """Crafted WriteOps with every lane species: CoW, in-place, dup-dst
    groups (leader carries cow_src — the write_pages convention), failed
    (dst=-1) and masked lanes. Row e-1 is the engine's reserved scratch."""
    ks = jax.random.split(KEY, 3)
    pool = jax.random.normal(ks[0], (e, page, d))
    payload = jax.random.normal(ks[1], (b, d))
    lane = jnp.arange(b, dtype=jnp.int32)
    # pair lanes 4k+1 onto lane 4k's dst (duplicate-dst groups)
    dst = jnp.where(lane % 4 == 1, lane - 1, lane) * 3 % (e - 1)
    cow_src = jnp.where(lane % 4 == 0, (dst + 5) % (e - 1), -1)
    cow_src = cow_src.astype(jnp.int32)
    ok = lane % 7 != 6
    dst = jnp.where(lane % 11 == 10, -1, dst).astype(jnp.int32)  # failed
    ops = dbs.WriteOps(dst=dst, cow_src=jnp.where(dst >= 0, cow_src, -1),
                       ok=ok & (dst >= 0))
    blocks = (lane * 5) % page          # multi-block spans within a group
    ref = make_kernel("xla").write(pool, ops, payload, blocks)
    out = make_kernel(kernel).write(pool, ops, payload, blocks)
    _assert_rows_equal(out, ref)
    # scratch-row masking: no masked/failed lane leaked into a live row
    untouched = set(range(e - 1)) - {int(x) for x in np.asarray(dst) if x >= 0}
    for i in untouched:
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(pool[i]))


@pytest.mark.parametrize("kernel", ["pallas", "ref", "copy"])
def test_write_matches_xla_on_write_pages_ops(kernel):
    """Ops produced by the real control plane, CoW pressure included."""
    st = dbs.make_state(64, 2, 16)
    st, vol = dbs.create_volume(st)
    pool = jax.random.normal(KEY, (65, 8, 4))   # +1 scratch row
    pages = jnp.arange(8) % 5                    # duplicate pages -> groups
    bits = jnp.full((8,), 1, jnp.uint32)
    st, ops = dbs.write_pages(st, vol, pages, bits)
    payload = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    blocks = jnp.arange(8, dtype=jnp.int32) % 8
    pool = dbs.apply_write_ops(pool, ops, payload, blocks)
    st, _ = dbs.snapshot(st, vol)
    mask = jnp.arange(8) % 2 == 0               # masked lanes ride along
    st, ops = dbs.write_pages(st, vol, pages, bits, mask)
    assert bool(jnp.any(ops.cow_src >= 0)), "expected CoW lanes"
    # the WriteOps contract the kernels rely on: no CoW source is a live
    # destination of the same batch
    live_dst = set(np.asarray(ops.dst)[np.asarray(ops.live())].tolist())
    assert live_dst.isdisjoint(np.asarray(ops.cow_src).tolist())
    payload2 = jax.random.normal(jax.random.PRNGKey(3), (8, 4))
    ref = make_kernel("xla").write(pool, ops, payload2, blocks)
    out = make_kernel(kernel).write(pool, ops, payload2, blocks)
    _assert_rows_equal(out, ref)


@pytest.mark.parametrize("e,page,d,b", [(16, 4, 8, 8), (33, 8, 16, 20)])
@pytest.mark.parametrize("kernel", ["pallas", "ref", "copy"])
def test_read_matches_xla_with_holes(kernel, e, page, d, b):
    """Hole lanes (ext < 0 — never-written or unmapped pages) must read as
    zeros, not as clamped extent 0's payload."""
    pool = jax.random.normal(KEY, (e, page, d))
    lane = jnp.arange(b, dtype=jnp.int32)
    ext = jnp.where(lane % 3 == 0, -1, (lane * 7) % e).astype(jnp.int32)
    blocks = (lane * 3) % page
    ref = make_kernel("xla").read(pool, ext, blocks)
    out = make_kernel(kernel).read(pool, ext, blocks)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert not np.asarray(out[0]).any()          # ext=-1 lane is zeros


def test_rw_pool_wrappers_multidim_payload():
    """The pool wrappers flatten/restore trailing payload dims."""
    e, page, shape, b = 10, 4, (2, 3), 6
    pool = jax.random.normal(KEY, (e, page) + shape)
    payload = jax.random.normal(jax.random.PRNGKey(1), (b,) + shape)
    lane = jnp.arange(b, dtype=jnp.int32)
    ops = dbs.WriteOps(dst=lane, cow_src=jnp.full((b,), -1, jnp.int32),
                       ok=jnp.ones((b,), bool))
    blocks = lane % page
    out = dbs_rw_write_pool(pool, ops, payload, blocks)
    ref = make_kernel("xla").write(pool, ops, payload, blocks)
    _assert_rows_equal(out, ref)
    ext = jnp.asarray([0, -1, 2, 5, -1, 3], jnp.int32)
    got = dbs_rw_read_pool(pool, ext, blocks)
    assert got.shape == (b,) + shape
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(make_kernel("xla").read(
                                      pool, ext, blocks)))


def test_write_and_read_vmap_safe():
    """The sharded path vmaps the step over a leading shard axis — kernels
    must produce per-shard results identical to the unmapped calls."""
    e, page, d, b, s = 12, 4, 8, 6, 3
    pools = jax.random.normal(KEY, (s, e, page, d))
    payloads = jax.random.normal(jax.random.PRNGKey(1), (s, b, d))
    lane = jnp.arange(b, dtype=jnp.int32)
    ops = dbs.WriteOps(dst=(lane * 2) % (e - 1),
                       cow_src=jnp.where(lane % 2 == 0, (lane + 3) % (e - 1),
                                         -1).astype(jnp.int32),
                       ok=lane % 5 != 4)
    blocks = lane % page
    ext = jnp.where(lane % 3 == 0, -1, lane).astype(jnp.int32)
    kern = make_kernel("pallas")
    vw = jax.vmap(lambda p, pay: kern.write(p, ops, pay, blocks))
    vr = jax.vmap(lambda p: kern.read(p, ext, blocks))
    w, r = vw(pools, payloads), vr(pools)
    for i in range(s):
        _assert_rows_equal(w[i], kern.write(pools[i], ops, payloads[i],
                                            blocks), excl_dump=False)
        np.testing.assert_array_equal(np.asarray(r[i]),
                                      np.asarray(kern.read(pools[i], ext,
                                                           blocks)))


# ---------------------------------------------------------------------------
# hypothesis property test (self-skips where hypothesis isn't installed)
# ---------------------------------------------------------------------------
def test_write_read_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st_

    E, PAGE, D, B = 12, 4, 8, 10
    compiled = not default_interpret()

    @settings(max_examples=25, deadline=None)
    @given(data=st_.data())
    def prop(data):
        dst = jnp.asarray(data.draw(st_.lists(
            st_.integers(-1, E - 2), min_size=B, max_size=B)), jnp.int32)
        ok = jnp.asarray(data.draw(st_.lists(
            st_.booleans(), min_size=B, max_size=B)))
        cow = jnp.asarray(data.draw(st_.lists(
            st_.integers(-1, E - 2), min_size=B, max_size=B)), jnp.int32)
        blocks = jnp.asarray(data.draw(st_.lists(
            st_.integers(0, PAGE - 1), min_size=B, max_size=B)), jnp.int32)
        ext = jnp.asarray(data.draw(st_.lists(
            st_.integers(-1, E - 1), min_size=B, max_size=B)), jnp.int32)
        # normalize to the write_pages convention: cow_src only on the
        # FIRST live lane of each dst group (the group leader)
        live = ok & (dst >= 0)
        same = live[None, :] & live[:, None] & (dst[None, :] == dst[:, None])
        leader = jnp.argmax(same, axis=1)
        is_leader = live & (leader == jnp.arange(B))
        cow = jnp.where(is_leader, cow, -1)
        ops = dbs.WriteOps(dst=dst, cow_src=cow, ok=ok)
        # compiled, the Pallas kernels (pallas, and copy's CoW half)
        # stream rows, so they alone are held to the second WriteOps
        # convention too: no CoW source is a live destination of the batch
        # (write_pages honours it, see test_dbs_properties); everything
        # else takes the full draw
        cow_is_dst = jnp.any(live[None, :] & (dst[None, :] == cow[:, None]),
                             axis=1)
        streamed = dbs.WriteOps(dst=dst, cow_src=jnp.where(cow_is_dst, -1,
                                                           cow), ok=ok)
        pool = jax.random.normal(KEY, (E, PAGE, D))
        payload = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        for name in ("pallas", "ref", "copy"):
            o = streamed if name != "ref" and compiled else ops
            ref = make_kernel("xla").write(pool, o, payload, blocks)
            out = make_kernel(name).write(pool, o, payload, blocks)
            _assert_rows_equal(out, ref)
        rref = make_kernel("xla").read(pool, ext, blocks)
        for name in ("pallas", "ref", "copy"):
            got = make_kernel(name).read(pool, ext, blocks)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(rref))

    prop()


# ---------------------------------------------------------------------------
# 2. the registry API
# ---------------------------------------------------------------------------
def test_registry_lists_and_rejects():
    names = available_kernels()
    for built_in in ("pallas", "xla", "ref", "copy"):
        assert built_in in names
    with pytest.raises(ValueError, match="unknown kernel"):
        make_kernel("nope")
    with pytest.raises(ValueError, match="unknown kernel"):
        Engine(EngineConfig(kernel="nope"))
    with pytest.raises(ValueError):
        register_kernel("broken", lambda *a: None)       # read= missing


def test_register_custom_kernel_roundtrip():
    xla = make_kernel("xla")
    calls = []

    def write(pool, ops, payload, blocks):
        calls.append("w")
        return xla.write(pool, ops, payload, blocks)

    try:
        register_kernel("traced", write, read=xla.read)
        assert "traced" in available_kernels()
        eng = Engine(EngineConfig(comm="fused", kernel="traced",
                                  payload_shape=(8,), n_extents=64,
                                  max_pages=32, batch=8))
        vol = eng.create_volume()
        eng.submit(Request(req_id=0, kind="write", volume=vol, page=0,
                           block=0, payload=jnp.ones((8,))))
        assert eng.drain() == 1
        assert calls, "custom kernel was not dispatched"
    finally:
        _REGISTRY.pop("traced", None)


def test_resolve_kernel_name_legacy_cow():
    """kernel= wins; kernel="auto" follows the legacy cow axis."""
    assert resolve_kernel_name(EngineConfig(kernel="ref")) == "ref"
    assert resolve_kernel_name(EngineConfig(cow="pallas")) == "pallas"
    assert resolve_kernel_name(EngineConfig(cow="ref")) == "xla"
    auto = resolve_kernel_name(EngineConfig())
    assert auto == ("pallas" if jax.default_backend() == "tpu" else "xla")
    assert isinstance(make_kernel(auto), DBSKernel)


# ---------------------------------------------------------------------------
# 3. end-to-end: pallas == xla volume bytes through the public API
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,shards", [("fused", 1), ("sharded", 2),
                                            ("ring", 2)])
def test_blockdev_bytes_pallas_vs_xla(backend, shards):
    """Identical op streams through two VolumeManagers differing only in
    ``kernel=``: full-device reads must be byte-identical, and both must
    match a host bytearray shadow (the byte oracle)."""
    def mgr(kernel):
        return VolumeManager(backend=backend, n_shards=shards, kernel=kernel,
                             payload_elems=8, page_blocks=4, max_pages=8,
                             n_extents=256, max_volumes=8, batch=16,
                             n_replicas=2)

    mgrs = {k: mgr(k) for k in ("pallas", "xla")}
    vols = {k: m.create() for k, m in mgrs.items()}
    shadow = bytearray(mgrs["pallas"].capacity)

    def pat(seed, n):
        return bytes((seed * 37 + i) % 251 for i in range(n))

    def write(off, data):
        for k in mgrs:
            vols[k].pwrite(off, data)
        shadow[off:off + len(data)] = data

    write(0, pat(1, 17))            # unaligned tail
    write(5, pat(2, 11))            # unaligned head+tail (read-modify-write)
    write(24, pat(3, 48))           # page-crossing span
    for k in mgrs:
        vols[k].snapshot()
    write(13, pat(4, 9))            # CoW overwrite
    write(40, pat(5, 24))           # CoW page-crossing
    for m in mgrs.values():
        m.flush()
    got = {k: vols[k].read(0, mgrs[k].capacity) for k in mgrs}
    assert got["pallas"] == got["xla"]
    assert got["pallas"] == bytes(shadow)
    for m in mgrs.values():
        m.close()


def test_harness_scenario_kernel_pallas():
    """One chaos-harness scenario on the ring backend with the Pallas
    kernels: the byte oracle must hold end to end (registry -> EngineConfig
    -> ring_step_core -> dbs_rw)."""
    from repro.harness import run_scenario
    res = run_scenario("control/ring", n_ops=60, kernel="pallas")
    res.raise_if_failed()
    assert res.checked_reads > 0


# ---------------------------------------------------------------------------
# 4. the stale-interpret fix (per-call resolution)
# ---------------------------------------------------------------------------
def test_dbs_copy_resolves_interpret_per_call(monkeypatch):
    """The old module-level ``@jax.jit`` captured ``default_interpret()`` at
    first trace; after that, backend changes silently reused the stale mode.
    Now every call must consult ``default_interpret`` (the static arg keys
    the jit cache)."""
    from repro.kernels.dbs import ops
    calls = []
    real = ops.default_interpret
    monkeypatch.setattr(ops, "default_interpret",
                        lambda: (calls.append(1), real())[1])
    pool = jnp.zeros((4, 2, 8))
    idx = jnp.asarray([0, 1], jnp.int32)
    mask = jnp.ones((2,), bool)
    ops.dbs_copy(pool, idx, idx, mask)
    n = len(calls)
    assert n >= 1
    ops.dbs_copy(pool, idx, idx, mask)      # same shapes: jit cache hit...
    assert len(calls) == n + 1              # ...but the mode is re-resolved
