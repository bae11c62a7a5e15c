"""Faults planted under the timed path, to show that the check catches
them, and the controls that break a guarantee a configuration states.

Each is a wrapper around the ring step (``repro.core.ring.ring_step_core``)
that the engine traces into its programs. ``arm(mgr, name)`` installs it
and drops the manager's compiled steps, so the programs that the warm-up
and the window run are built with the fault; set-up (the prefill) runs
unbroken. ``disarm()`` puts the original step back.

Faults (what a run can have):

- ``state_unchanged``: the step returns the replica states and pools it
  was given, and no read data;
- ``half_batch``: the upper half of the batch's lanes are acknowledged
  but not performed (turned into no-ops);
- ``answer_altered``: the first byte of lane 0's payload is changed where
  the step receives it (writes), and of lane 0's read data where the step
  produces it (reads).

(The exchange between chips does not exist in a one-chip cell.)

Controls (a guarantee broken):

- ``ack_one_replica``: a write is acknowledged with only replica 0
  written — the 3-replica write guarantee;
- ``tile_row0``: a read returns the first block of the aligned 8-block
  tile that holds its block — the read guarantee (the shortcut the read
  kernel's 8-block tile invites).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

FAULTS = ("state_unchanged", "half_batch", "answer_altered")
CONTROLS = ("ack_one_replica", "tile_row0")

_ORIGINAL = []


def _wrap(step, name: str):
    from repro.core import ring

    def broken(table, cq, states, pools, page_revs, batch, rr, healthy,
               **kw):
        lane = jnp.arange(batch.op.shape[0])
        if name == "half_batch":
            batch = dataclasses.replace(batch, op=jnp.where(
                lane >= batch.op.shape[0] // 2, ring.OP_NOOP, batch.op))
        elif name == "answer_altered":
            is_w0 = (lane == 0) & (batch.op == ring.OP_WRITE)
            pay = batch.payload.reshape(batch.payload.shape[0], -1)
            pay = pay.at[:, 0].set(jnp.where(
                is_w0, jnp.mod(pay[:, 0] + 1, 256), pay[:, 0]))
            batch = dataclasses.replace(
                batch, payload=pay.reshape(batch.payload.shape))
        elif name == "tile_row0":
            batch = dataclasses.replace(batch, block=jnp.where(
                batch.op == ring.OP_READ, batch.block // 8 * 8, batch.block))
        out = step(table, cq, states, pools, page_revs, batch, rr, healthy,
                   **kw)
        t, c, st, pl, pr, h, view = out
        if name == "state_unchanged":
            st, pl, pr = states, pools, page_revs
            view = dataclasses.replace(view,
                                       reads=jnp.zeros_like(view.reads))
        elif name == "answer_altered":
            r = view.reads.reshape(view.reads.shape[0], -1)
            is_r0 = (lane == 0) & (batch.op == ring.OP_READ)
            r = r.at[:, 0].set(jnp.where(is_r0, jnp.mod(r[:, 0] + 1, 256),
                                         r[:, 0]))
            view = dataclasses.replace(view,
                                       reads=r.reshape(view.reads.shape))
        elif name == "ack_one_replica" and pl:
            pl = (pl[0],) + tuple(pools[1:])
        return t, c, st, pl, pr, h, view
    return broken


def arm(mgr, name: str) -> None:
    """Build ``mgr``'s ring programs from here on with ``name`` planted."""
    if name not in FAULTS + CONTROLS:
        raise ValueError(f"unknown fault {name!r}; known: "
                         f"{FAULTS + CONTROLS}")
    from repro.core import ring
    disarm()
    _ORIGINAL.append(ring.ring_step_core)
    ring.ring_step_core = _wrap(ring.ring_step_core, name)
    mgr.engine.impl._steps.clear()


def disarm() -> None:
    from repro.core import ring
    if _ORIGINAL:
        ring.ring_step_core = _ORIGINAL.pop()
