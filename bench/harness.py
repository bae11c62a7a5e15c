"""One run of one cell: set-up, a closed-loop window, the check.

Set-up (``setup_s`` runs from process start to the first timed call):

1. ``VolumeManager(backend="ring", **geometry)`` with the configuration's
   volumes created.
2. Prefill: one block write per page through the byte API allocates every
   page of every volume; then each page's rows are filled on the device
   from the seed (``data.prefill_bytes_jnp``), one donated in-place
   program per replica pool.
3. Warm-up: the cell's own closed loop for ``WARM_PUMPS`` pumps, and
   until every kind of call the mix issues has completed, so the window
   compiles nothing.

Window: the generator keeps ``qd`` byte-API calls (``Volume.pwrite`` /
``Volume.pread``) in flight, calls ``VolumeManager.pump()``, harvests the
futures that are ``done()`` and submits the next calls. A call's latency
runs from just before its API call to just after its result was taken
after the pump that completed it. The window closes at the first harvest
at or past ``seconds``; rates are over all calls completed in it.

With ``trace``, a JAX profiler trace covers a steady stretch in the
middle of the window (``bench.window``), with the host spans
``bench.submit``, ``bench.pump`` and ``bench.harvest``, and is reduced in
this process (``trace_reduce``).

Check: the run is drained, the device's peak memory read, and then what
the window produced is compared with the plain reference
(``reference``): a seeded sample of the reads' returned bytes, and every
replica's pool read through its own extent map. Every comparison is exact
(limit 0).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import shutil
import tempfile
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, kernels, reference, stats, trace_reduce
from bench.loader import Cell
from bench.traffic import Generator

WARM_PUMPS = 16
READ_SAMPLE_SHARE = 1 / 8     # share of reads kept for the check
TRACE_LEAD_S = 1.0            # traced stretch starts this far in ...
TRACE_SECONDS = 4.0           # ... and lasts at most this long
MIB = 1 << 20


class Compiles:
    """Counts JAX compile events (tracing, lowering, compiling) through
    ``jax.monitoring``; registered once per process."""
    n = 0
    seconds = 0.0
    _registered = False

    @classmethod
    def register(cls) -> None:
        if cls._registered:
            return

        def on(event: str, secs: float, **_):
            if event.startswith("/jax/core/compile/"):
                cls.n += 1
                cls.seconds += secs
        jax.monitoring.register_event_duration_secs_listener(on)
        cls._registered = True


# ---------------------------------------------------------------- set-up
@partial(jax.jit, donate_argnums=(0,))
def _fill_pool(pool, table, keys, filled):
    """Fill every row of a (1, E+1, page, D) pool that the (1, V, P) extent
    map gives to a prefilled volume (``filled``) with that page's prefill
    bytes, in place."""
    _, e1, pb, bb = pool.shape
    tbl = table[0]
    v, p = tbl.shape
    e = jnp.where((tbl >= 0) & filled[:, None], tbl, e1)
    owner_key = jnp.zeros((e1,), jnp.uint32).at[e].set(
        jnp.broadcast_to(keys[:, None], (v, p)), mode="drop")
    owner_page = jnp.full((e1,), -1, jnp.int32).at[e].set(
        jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32)[None, :], (v, p)),
        mode="drop")
    offs = ((owner_page.astype(jnp.uint32)[:, None, None] * pb
             + jnp.arange(pb, dtype=jnp.uint32)[None, :, None]) * bb
            + jnp.arange(bb, dtype=jnp.uint32)[None, None, :])
    val = data.prefill_bytes_jnp(owner_key[:, None, None], offs)
    out = jnp.where((owner_page >= 0)[:, None, None],
                    val.astype(pool.dtype), pool[0])
    return out[None]


def build(cell: Cell, seed: int):
    """The manager at the configuration's geometry, its volumes and their
    prefill keys."""
    from repro.core.blockdev import VolumeManager
    mgr = VolumeManager(backend="ring", **cell.config["geometry"])
    vols = [mgr.create() for _ in range(int(cell.config["volumes"]))]
    keys = [data.volume_key(seed, i) for i in range(len(vols))]
    return mgr, vols, keys


def prefill(mgr, vols, keys) -> None:
    """Allocate every page through the byte API, then fill the rows on the
    device from the seed."""
    bb, pby = mgr.block_bytes, mgr.page_bytes
    zero = bytes(bb)
    n_pages = mgr.capacity // pby
    for vol in vols:
        futs = [vol.pwrite(p * pby, zero) for p in range(n_pages)]
        mgr.flush()
        for f in futs:
            f.result()
    storage = mgr.engine.backend
    states, pools, _ = storage.device_state()
    v = states[0].table.shape[1]
    key_arr = np.zeros(v, np.uint32)
    filled = np.zeros(v, bool)
    for vol, key in zip(vols, keys):
        key_arr[vol.vid] = key
        filled[vol.vid] = True
    key_arr, filled = jnp.asarray(key_arr), jnp.asarray(filled)
    new = []
    for st, pool in zip(states, pools):
        new.append(_fill_pool(pool, st.table, key_arr, filled))
    del pools
    storage.set_device_state(states, tuple(new))
    jax.block_until_ready(new)


# ---------------------------------------------------------------- the loop
class Loop:
    """The closed loop: ``qd`` calls in flight, one pump at a time."""

    def __init__(self, mgr, vols, cell: Cell, seed: int):
        self.mgr = mgr
        self.vols = vols
        self.mix = cell.mix
        self.bb = mgr.block_bytes
        self.gen = Generator(cell.mix, seed=seed, n_volumes=len(vols),
                             volume_bytes=mgr.capacity, block_bytes=self.bb)
        self.payloads = data.Payloads(seed, self.bb)
        rng = np.random.default_rng([seed % (1 << 64), 0x5A])
        self.sample = (rng.random(4096) < READ_SAMPLE_SHARE).tolist()
        self.inflight: collections.deque = collections.deque()
        self.log: List[reference.Op] = []
        self.sampled: Dict[int, bytes] = {}
        self.call = 0
        self.failed = 0
        self.annotate = False
        self.recording = False
        self.reset()

    def reset(self) -> None:
        """Zero what the window records."""
        self.lat = {"read": [], "write": []}
        self.done_calls = 0
        self.done_bytes = 0
        self.done_blocks = {"read": 0, "write": 0}
        self.spans = {"submit": [0, 0.0], "pump": [0, 0.0],
                      "harvest": [0, 0.0]}
        self.submitted = 0
        self.kinds_done = collections.Counter()

    def _span(self, name: str):
        return (jax.profiler.TraceAnnotation("bench." + name)
                if self.annotate else contextlib.nullcontext())

    def fill(self) -> None:
        qd, bb, clock = self.mix.qd, self.bb, time.perf_counter
        sp = self.spans["submit"]
        while len(self.inflight) < qd:
            is_read, v, off, n = self.gen.next()
            c = self.call
            self.call += 1
            payload = None if is_read else self.payloads.data(c, n // bb)
            with self._span("submit"):
                t0 = clock()
                fut = (self.vols[v].pread(off, n) if is_read
                       else self.vols[v].pwrite(off, payload))
                t1 = clock()
            sp[0] += 1
            sp[1] += t1 - t0
            self.log.append((is_read, v, off, n, c))
            self.inflight.append((fut, t0, is_read, n, c))
            self.submitted += 1

    def pump(self) -> None:
        with self._span("pump"):
            t0 = time.perf_counter()
            self.mgr.pump()
            t1 = time.perf_counter()
        sp = self.spans["pump"]
        sp[0] += 1
        sp[1] += t1 - t0

    def harvest(self) -> float:
        """Take every finished call's result; returns the clock after."""
        clock = time.perf_counter
        h0 = clock()
        keep = collections.deque()
        with self._span("harvest"):
            for item in self.inflight:
                fut, t0, is_read, n, c = item
                if not fut.done():
                    keep.append(item)
                    continue
                try:
                    val = fut.result()
                except OSError:
                    self.failed += 1
                    continue
                now = clock()
                kind = "read" if is_read else "write"
                if is_read and self.sample[c & 4095]:
                    self.sampled[c] = val
                self.kinds_done[kind] += 1
                if self.recording:
                    self.lat[kind].append(now - t0)
                    self.done_calls += 1
                    self.done_bytes += n
                    self.done_blocks[kind] += n // self.bb
        self.inflight = keep
        now = clock()
        sp = self.spans["harvest"]
        sp[0] += 1
        sp[1] += now - h0
        return now

    def step(self) -> float:
        self.fill()
        self.pump()
        return self.harvest()

    def drain(self) -> None:
        """Complete every call still in flight (outside the window)."""
        self.mgr.flush()
        self.harvest()
        self.failed += len(self.inflight)     # never completed
        self.inflight.clear()


# ---------------------------------------------------------------- the run
class Tracer:
    """Traces a steady stretch of the window: from ``start`` to ``stop``
    (host clock), switched at pump boundaries. Records the host spans and
    counters over the stretch and reduces the trace once it is off."""

    def __init__(self, loop: Loop, start: float, stop: float):
        self.loop, self.start, self.stop = loop, start, stop
        self.on = self.done = False

    def _counters(self) -> Dict[str, float]:
        impl = self.loop.mgr.engine.impl
        out = {"completed": impl.completed, "dispatches": impl.dispatches}
        out.update({f"{k}_blocks": n
                    for k, n in self.loop.done_blocks.items()})
        out.update({k: tuple(v) for k, v in self.loop.spans.items()})
        return out

    def tick(self, now: float) -> None:
        if not self.on and not self.done and now >= self.start:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            # the Python function tracer and the HLO dump would multiply the
            # trace's size and slow the host
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.window = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            self.window.__enter__()
            self.loop.annotate = self.on = True
            self.c0 = self._counters()
        elif self.on and now >= self.stop:
            self.loop.annotate = self.on = False
            self.window.__exit__(None, None, None)
            c1 = self._counters()
            jax.profiler.stop_trace()
            self.done = True
            self.spans = {k: (c1[k][0] - self.c0[k][0],
                              c1[k][1] - self.c0[k][1])
                          for k in self.loop.spans}
            self.counters = {k: c1[k] - self.c0[k] for k in c1
                             if k not in self.loop.spans}

    def reduce(self, trace_ops: Optional[Callable]) -> trace_reduce.Reduction:
        try:
            return trace_reduce.reduce_dir(
                self.dir, **({} if trace_ops is None
                             else {"is_device_op": trace_ops}))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_info() -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Reading:
    """What a per-layer metric's reader gets: the traced stretch's host
    spans as (count, seconds), its counters, the trace reduction, the
    geometry and the chip's peaks."""

    def __init__(self, geometry: Dict, spans, counters,
                 trace: Optional[trace_reduce.Reduction], peaks):
        self.geometry = geometry
        self.spans = spans
        self.counters = counters
        self.trace = trace
        self.peaks = peaks

    def span_mean(self, name: str) -> Optional[float]:
        n, s = self.spans.get(name, (0, 0.0))
        return s / n if n else None


def _end_to_end(cell: Cell, loop: Loop, window_s: float,
                setup_s: float) -> Dict[str, Dict[str, Any]]:
    values = {"setup_s": setup_s,
              "iops": stats.rate(loop.done_calls, window_s),
              "bandwidth_mib_s": stats.rate(loop.done_bytes / MIB, window_s)}
    for kind, lat in loop.lat.items():
        if lat:
            values[f"{kind}_p99_ms"] = 1e3 * stats.percentile(lat, 99)
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise RuntimeError(f"metric {m['name']} has no reading in cell "
                               f"{cell.name}")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def _check(cell: Cell, mgr, vols, keys, loop: Loop) -> Dict[str, Dict]:
    """Compare what the run produced with the reference: the sampled reads,
    and every replica's pool through its own extent map."""
    replay = reference.Replay(loop.log, loop.bb, loop.sampled)
    checks: Dict[str, Dict[str, Any]] = {
        "failed_calls": {"value": loop.failed, "limit": 0}}
    if "read" in cell.mix.kinds:
        bad, n_reads = reference.read_mismatch_bytes(
            replay, loop.sampled, loop.payloads.pool, keys)
        checks["read_mismatch_bytes"] = {"value": bad, "limit": 0}
        checks["reads_compared"] = {"value": n_reads, "at_least": 1}
    states, pools, _ = mgr.engine.backend.device_state()
    bad, holes = reference.replica_mismatch_bytes(
        pools, [st.table for st in states], [v.vid for v in vols], keys,
        replay, loop.payloads.pool, mgr.capacity // mgr.page_bytes,
        mgr.page_blocks)
    for r, b in enumerate(bad):
        checks[f"replica{r}_mismatch_bytes"] = {"value": b, "limit": 0}
    checks["unmapped_pages"] = {"value": sum(holes), "limit": 0}
    return checks


def passed(check: Dict[str, Any]) -> bool:
    if "at_least" in check:
        return check["value"] >= check["at_least"]
    return check["value"] <= check["limit"]


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, after_setup: Optional[Callable] = None,
             peaks: Optional[Dict[str, float]] = None,
             trace_ops: Optional[Callable] = None) -> Dict[str, Any]:
    """Run ``cell`` once; returns the result line as a dict (the checks
    last). ``t_start`` is the process start on the ``perf_counter`` clock;
    ``after_setup(mgr)`` runs between prefill and warm-up; ``trace_ops``
    picks the device operations in the trace (``trace_reduce``'s TPU
    default when None)."""
    Compiles.register()
    phases = {"start": time.perf_counter() - t_start}
    t = time.perf_counter()
    mgr, vols, keys = build(cell, seed)
    phases["build"] = time.perf_counter() - t
    t = time.perf_counter()
    prefill(mgr, vols, keys)
    phases["prefill"] = time.perf_counter() - t
    if after_setup is not None:
        after_setup(mgr)
    t = time.perf_counter()
    loop = Loop(mgr, vols, cell, seed)
    pumps = 0
    while pumps < WARM_PUMPS or any(loop.kinds_done[k] == 0
                                    for k in cell.mix.kinds):
        loop.step()
        pumps += 1
        if pumps > 100 * WARM_PUMPS:
            raise RuntimeError("warm-up never completed every kind of call")
    phases["warmup"] = time.perf_counter() - t
    setup_compile_s = Compiles.seconds
    # start the window with no garbage, and keep set-up's objects out of
    # the collections the window may trigger
    gc.collect()
    gc.freeze()

    # ---- the window
    loop.reset()
    loop.recording = True
    n_compiles = Compiles.n
    dispatches0 = mgr.engine.impl.dispatches
    gc2 = gc.get_stats()[2]["collections"]
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    tracer = None
    if trace:
        lead = min(TRACE_LEAD_S, seconds / 4)
        tracer = Tracer(loop, t0 + lead,
                        t0 + lead + min(TRACE_SECONDS, seconds - 2 * lead))
    end = t0 + seconds
    while True:
        now = loop.step()
        if tracer is not None:
            tracer.tick(now)
        if now >= end and (tracer is None or tracer.done):
            break
    window_s = now - t0
    window_compiles = Compiles.n - n_compiles
    # ring steps the loop's own pumps did not dispatch: drains of the
    # byte API's hazard fence
    fence_steps = (mgr.engine.impl.dispatches - dispatches0
                   - loop.spans["pump"][0])
    gc2 = gc.get_stats()[2]["collections"] - gc2
    loop.recording = False
    loop.drain()
    gc.unfreeze()
    device = dict(device_info(), memory_peak_bytes=memory_peak_bytes())

    # ---- the metrics
    breakdown = None
    if tracer is None:
        metrics = _end_to_end(cell, loop, window_s, setup_s)
    else:
        red = tracer.reduce(trace_ops)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = Reading(cell.config["geometry"], tracer.spans, tracer.counters,
                      red, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": red.top_ops(10, kernels.label),
                     "idle_gaps": [[n, s] for n, s in red.gaps[:10]]}

    # ---- the check, outside the window
    t = time.perf_counter()
    checks = _check(cell, mgr, vols, keys, loop)
    mgr.close()
    check_s = time.perf_counter() - t

    out = {"correct": loop.call > 0 and all(map(passed, checks.values())),
           "attempted": loop.call, "failed": loop.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["info"] = {"setup_compile_s": setup_compile_s,
                   "setup_phases_s": phases,
                   "window_compiles": window_compiles,
                   "window_s": window_s, "check_s": check_s,
                   "completed_calls": loop.done_calls,
                   "calls": {k: len(v) for k, v in loop.lat.items()},
                   "fence_steps": fence_steps, "gc_full_collections": gc2,
                   "latency_ms": {k: {f"p{q}": 1e3 * stats.percentile(v, q)
                                      for q in (50, 90, 99, 100)}
                                  for k, v in loop.lat.items() if v},
                   "p99_beyond": {k: stats.beyond(len(v), 99)
                                  for k, v in loop.lat.items() if v}}
    out["checks"] = checks
    return out
