"""The three-node cell, ``randwrite4k-qd64.3r-3node``, rehearsed at a tiny
geometry on four virtual CPU devices (``XLA_FLAGS`` must be set before jax
is imported, so the runs happen in a child python, once for the module):
untraced and traced it is correct with every replica checked on its own
chip, compiles nothing in its window, reports exactly its end-to-end
metrics, and its new per-layer readers give a number or nothing, never 0;
a corrupted row of replica 2 is caught on replica 2 alone, and a step
that leaves the fan-out out reads not correct.

The entries below are the ones ``BENCHMARK.json`` holds for the cell; the
harness and its readers run it unedited."""
import json
import os
import subprocess
import sys
import textwrap

import bench_tiny
import pytest

from bench import loader

NAME = "randwrite4k-qd64.3r-3node"
CONFIG = {
    "name": "longhorn-3r-3node",
    "source": "https://longhorn.io/docs/1.7.0/references/settings/"
              "#replica-node-level-soft-anti-affinity",
    "file": "bench/configs/longhorn-3r-3node.json",
    "reduced": ["max_pages", "n_extents"],
    "why": "Longhorn's 3 default replicas on a 3-node cluster, one replica "
           "per node by hard anti-affinity: each replica on its own chip, "
           "every write mirrored from the engine's chip over ICI"}
CELL = {
    "name": NAME, "config": "longhorn-3r-3node",
    "traffic": "randwrite4k-qd64", "chips": 4,
    "why": "fio randwrite 4 KiB at qd 64 over 4 prefilled 288 MiB volumes, "
           "3 replicas each on its own chip: the fan-out over ICI and the "
           "acknowledgement across chips do most of the new work"}
APPENDED_TO = ("iops", "write_p99_ms", "api_us_per_call", "pump_ms",
               "blocks_per_pump", "device_idle_pct", "dbs_rw_write_roofline")
FANOUT_METRICS = [
    {"name": name, "unit": unit, "better": better, "source": "device_trace",
     "layer": "replica fan-out (ICI)", "moves": "iops", "workloads": [NAME]}
    for name, unit, better in (("replica_fanout_ms", "ms", "lower"),
                               ("replica_fanout_roofline", "%", "higher"))]


CHILD = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import sys
    sys.path.insert(0, {str(bench_tiny.ROOT / "tests" / "bench")!r})
    import bench_tiny
    import numpy as np
    from pathlib import Path
    from bench import harness

    name = {NAME!r}
    cell = bench_tiny.tiny_cell(name, Path(sys.argv[1]))
    out = {{"untraced": bench_tiny.run_tiny(name, seed=2 ** 31 + 17,
                                           cell=cell),
            "traced": bench_tiny.run_tiny(name, seed=2 ** 31 + 19,
                                         cell=cell, seconds=0.6,
                                         trace=True)}}

    seed = 2 ** 31 + 23
    mgr, vols, keys = harness.build(cell, seed)
    harness.prefill(mgr, vols, keys)
    loop = harness.Loop(mgr, vols, cell, seed)
    for _ in range(8):
        loop.step()
    loop.drain()
    before = harness._check(cell, mgr, vols, keys, loop)
    storage = mgr.engine.backend
    states, pools, _ = storage.device_state()
    row = int(np.asarray(states[2].table)[0, vols[0].vid, 0])
    bad = pools[2].at[0, row, 3, 5].add(1.0)
    storage.set_device_state(states, tuple(pools[:2]) + (bad,))
    after = harness._check(cell, mgr, vols, keys, loop)
    out["corrupt"] = {{"before": before, "after": after,
                       "device": sorted(d.id for d in bad.devices())}}

    # the fan-out left out: nodes 1-2 keep their zero SQE blocks
    from repro.core import ring
    mirror = ring.mirror_write

    def skip_fanout(mgr):
        ring.mirror_write = lambda x, axis, src_index=0: x
        mgr.engine.impl._steps.clear()
    try:
        out["no_fanout"] = bench_tiny.run_tiny(name, seed=2 ** 31 + 29,
                                               cell=cell,
                                               after_setup=skip_fanout)
    except RuntimeError as e:           # the harness stops the run
        out["no_fanout"] = {{"raised": str(e)}}
    finally:
        ring.mirror_write = mirror
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def res():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CHILD, str(loader.BENCH_DIR)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("run", ["untraced", "traced"])
def test_three_node_cell_runs_correct(res, run):
    out = res[run]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["info"]["window_compiles"] == 0
    assert {f"replica{r}_mismatch_bytes" for r in range(3)} <= set(
        out["checks"])
    assert all(out["checks"][f"replica{r}_mismatch_bytes"]["value"] == 0
               for r in range(3))
    assert list(out)[-1] == "checks"


def test_three_node_cell_reports_its_end_to_end_metrics(res):
    m = res["untraced"]["metrics"]
    assert set(m) == {"iops", "write_p99_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())


def test_three_node_readers_give_a_number_or_nothing(res):
    m = res["traced"]["metrics"]
    cell = bench_tiny.tiny_cell(NAME)
    assert set(m) <= {x["name"] for x in cell.per_layer}
    assert {"api_us_per_call", "pump_ms", "blocks_per_pump",
            "device_idle_pct"} <= set(m)
    for name in ("replica_fanout_ms", "replica_fanout_roofline"):
        assert name not in m or m[name]["value"] > 0
    # the CPU rehearsal passes no interconnect peak
    assert "replica_fanout_roofline" not in m


def test_a_corrupted_row_of_replica_2_is_caught(res):
    before, after = res["corrupt"]["before"], res["corrupt"]["after"]
    assert res["corrupt"]["device"] == [2]
    for r in range(3):
        assert before[f"replica{r}_mismatch_bytes"]["value"] == 0
    assert after["replica2_mismatch_bytes"]["value"] > 0
    assert after["replica0_mismatch_bytes"]["value"] == 0
    assert after["replica1_mismatch_bytes"]["value"] == 0


def test_a_step_without_the_fan_out_is_caught(res):
    """Without the permutes the remote replicas see no SQE: no write is
    acknowledged, so the harness stops the run before its window."""
    assert res["no_fanout"] == {
        "raised": "warm-up never completed every kind of call"}


# the fan-out's operations as a v5e trace names them (longhorn-3r-3node)
START = ("%collective-permute-start.1 = (s32[1,262784]{1,0:T(1,128)}, "
         "s32[1,262784]{1,0:T(1,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
         "collective-permute-start(s32[1,262784]{1,0:T(1,128)} %param.62), "
         "channel_id=1, source_target_pairs={{0,1}}")
DONE = ("%collective-permute-done.1 = s32[1,262784]{1,0:T(1,128)S(1)} "
        "collective-permute-done((s32[1,262784]{1,0:T(1,128)}, "
        "s32[1,262784]{1,0:T(1,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
        "%collective-permute-start.1)")
SELECT = ("%broadcast_select_fusion.1 = s32[1,262784]{1,0:T(1,128)S(1)} "
          "fusion(s32[1,262784]{1,0:T(1,128)S(1)} %collective-permute-done, "
          "pred[]{:T(512)S(6)} %eq.95, s32[1,262784]{1,0:T(1,128)S(1)} "
          "%collective-permute-done.1, s32[1,262784]{1,0:T(1,128)} "
          "%param.62, pred[]{:T(512)S(6)} %eq.96), kind=kLoop")
ALL_REDUCE = ("%all-reduce = (s32[64]{0:T(128)S(1)}, s32[64,4096]{1,0:T(8,128)"
              "S(1)}) all-reduce(s32[64]{0:T(128)S(1)} %get-tuple-element.40, "
              "s32[64,4096]{1,0:T(8,128)S(1)} %fusion.31), channel_id=1, "
              "replica_groups={{0,1,2}}")


def test_fanout_ops_are_the_permutes_alone():
    from bench.fanout import FANOUT_OPS
    assert FANOUT_OPS.search(START) and FANOUT_OPS.search(DONE)
    assert not FANOUT_OPS.search(SELECT)
    assert not FANOUT_OPS.search(ALL_REDUCE)


def _reading(ops, counters, peaks):
    from bench import trace_reduce
    from bench.harness import Reading
    trace = trace_reduce.Reduction(window_s=2.0, busy_s=0.5,
                                   op_seconds=dict(ops), n_ops=len(ops))
    geometry = {"n_replicas": 3, "replica_chips": 3, "payload_elems": 4096}
    return Reading(geometry, {}, counters, trace, peaks)


def test_fanout_readers_share_of_each_chip():
    """Device seconds summed over the 3 chips count a third per chip; the
    roofline's work is 2 remote copies of every written 4 KiB block."""
    from bench import fanout, peaks
    from bench.loader import load_reader
    ms, roof = (load_reader("replica_fanout_ms"),
                load_reader("replica_fanout_roofline"))
    v5e = peaks.peaks("TPU v5 lite")
    ops = {START: 0.001, DONE: 0.005, SELECT: 0.003, ALL_REDUCE: 0.004}
    r = _reading(ops, {"dispatches": 100, "write_blocks": 6400}, v5e)
    assert ms(r) == pytest.approx(1e3 * 0.006 / 3 / 100)
    assert fanout.fanout_user_bytes(6400, r.geometry) == 6400 * 2 * 4096
    assert roof(r) == pytest.approx(
        100 * 6400 * 2 * 4096 / (0.002 * v5e["ici_bytes_per_s"]))
    assert roof(_reading(ops, {"dispatches": 100, "write_blocks": 6400},
                         {"hbm_bytes_per_s": 819e9})) is None
    assert roof(_reading(ops, {"dispatches": 100}, v5e)) is None
    none = {SELECT: 0.003, ALL_REDUCE: 0.004}
    assert ms(_reading(none, {"dispatches": 100}, v5e)) is None
    assert roof(_reading(none, {"write_blocks": 6400}, v5e)) is None


def test_the_entries_keep_the_benchmark_format():
    """``BENCHMARK.json`` holds exactly the entries above for the cell, at
    the end of their lists, in the format the benchmark requires: valid
    names, a ``why`` of at most 200 characters, the configuration's
    ``reduced`` as its file says, every metric's cells listed, at most half
    the cells on four chips."""
    bench = loader.load_benchmark()
    assert bench["configs"][-1] == CONFIG
    assert bench["workloads"][-1] == CELL
    assert bench["per_layer"][-2:] == FANOUT_METRICS
    cells = {w["name"]: w for w in bench["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    assert loader.load_config(CONFIG["name"])["reduced"] == CONFIG["reduced"]
    assert len(CELL["why"]) <= 200 and len(CONFIG["why"]) <= 200
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert loader.NAME.match(m["name"])
        assert all(c in cells for c in m.get("workloads", []))
        if m["name"] in APPENDED_TO:
            assert m["workloads"][-1] == NAME
    reported = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if NAME in m.get("workloads", [NAME])}
    assert reported == set(APPENDED_TO) | {"setup_s", "replica_fanout_ms",
                                           "replica_fanout_roofline"}
    assert len(json.dumps(bench)) < 64 * 1024
