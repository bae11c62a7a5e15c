"""Replica fan-out (ICI): device milliseconds per ring step that the step
spends sending the uploaded batch from node 0 to the other replica chips.

On a v5e (longhorn-3r-3node's trace) every replica chip's ``XLA Ops``
line holds one asynchronous pair per destination, named by its HLO text:
``%collective-permute-start = (s32[1,262784]{..}, ..) collective-permute-
start(s32[1,262784]{..} %param.62), channel_id=1, source_target_pairs=
{{0,2}}`` and ``%collective-permute-done = s32[1,262784]{..}
collective-permute-done((..) %collective-permute-start)``, and ``.1`` for
``{{0,1}}`` (``bench.fanout.FANOUT_OPS`` matches those opcodes). Time is
their device time in the traced stretch, summed over the chips and
divided by the replica chips (``replica_chips``), so it is each chip's
share; steps are the ring steps dispatched in the stretch
(Δ``dispatches``). None where the trace holds no such operation (every
replica on one chip)."""
from bench.fanout import FANOUT_OPS, replica_chips


def read(ctx):
    t = ctx.trace
    secs = t.seconds_of(FANOUT_OPS) if t is not None else 0.0
    steps = ctx.counters.get("dispatches", 0)
    if secs <= 0 or not steps:
        return None
    return 1e3 * secs / replica_chips(ctx.geometry) / steps
