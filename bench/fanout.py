"""The replica fan-out the per-layer metrics ``replica_fanout_ms`` and
``replica_fanout_roofline`` read: how its device operations are named in a
chip trace, and the least interconnect bytes the users' writes need of it.

With the replicas on separate chips (a configuration's ``replica_chips``),
the ring step uploads each batch to node 0 only and sends it on to the
other replica nodes inside the step, one point-to-point permute per
destination (``repro.core.replication.mirror_write``, under the named
scope ``replica_fanout``). A v5e trace (read off by hand,
longhorn-3r-3node) names each such operation on the ``XLA Ops`` line of
every replica chip by its HLO text, an asynchronous pair per
destination::

    %collective-permute-start.1 = (s32[1,262784]{1,0:T(1,128)}, ..,
      u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(
      s32[1,262784]{1,0:T(1,128)} %param.62), channel_id=1,
      source_target_pairs={{0,1}}
    %collective-permute-done.1 = s32[1,262784]{1,0:T(1,128)S(1)}
      collective-permute-done((s32[1,262784]{..}, ..)
      %collective-permute-start.1)

(``%collective-permute-start`` / ``-done`` carry ``{{0,2}}``.) The
``where`` that picks each node's copy is a fusion that names the permutes
among its operands (``%broadcast_select_fusion.1 = .. fusion(..
%collective-permute-done, ..)``), so ``FANOUT_OPS`` matches the opcode —
the name followed by its operand list — and not a mere mention. The read
return and the write acknowledgement are one combined ``all-reduce``,
which it does not match.
"""
from __future__ import annotations

import re
from typing import Dict

FANOUT_OPS = re.compile(r"collective-permute(?:-start|-done)?\(")


def replica_chips(geometry: Dict) -> int:
    """Chips the replicas span (1: all on one chip, no fan-out)."""
    return int(geometry.get("replica_chips", 1))


def fanout_user_bytes(blocks: int, geometry: Dict) -> int:
    """A block write to R replicas on R chips: the engine's chip holds one
    replica and sends the block to the other R - 1, one byte per user byte
    at least."""
    return blocks * (replica_chips(geometry) - 1) \
        * int(geometry["payload_elems"])
