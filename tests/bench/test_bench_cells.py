"""Each cell's mix, its generator and the reference comparison, rehearsed
at a tiny geometry on the CPU: the run is correct, compiles nothing in its
window, reports exactly the cell's metrics, and the reference catches a
corrupted replica row and a corrupted read."""
import bench_tiny
import numpy as np
import pytest

from bench import data, reference


@pytest.mark.parametrize("name", bench_tiny.CELLS)
def test_tiny_cell_runs_correct(name):
    cell = bench_tiny.tiny_cell(name)
    out = bench_tiny.run_tiny(name, seed=2 ** 31 + 11, cell=cell)
    assert out["correct"], out["checks"]
    assert out["info"]["window_compiles"] == 0
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    r = cell.config["geometry"]["n_replicas"]
    assert {f"replica{i}_mismatch_bytes" for i in range(r)} <= set(
        out["checks"])


def test_tiny_traced_run_reads_the_per_layer_metrics():
    name = "randrw4k-qd1.3r"
    out = bench_tiny.run_tiny(name, seconds=0.6, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    # the CPU trace has no dbs_rw kernel events: their rooflines are left
    # out, never reported as 0
    assert {"api_us_per_call", "pump_ms", "blocks_per_pump",
            "device_idle_pct"} <= set(m)
    assert not any(k.endswith("_roofline") for k in m)
    assert m["blocks_per_pump"]["value"] == pytest.approx(1.0)
    assert 0 < m["device_idle_pct"]["value"] < 100
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    bd = out["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert {n for n, _ in bd["idle_gaps"]} <= {"bench.submit", "bench.pump",
                                               "bench.harvest", "none"}


def _replay_and_image(seed=5, bb=64, n_blocks=32):
    """A two-volume image written by a small op log, with one read."""
    pay = data.Payloads(seed, bb)
    log = [(False, 0, 0, 2 * bb, 0), (False, 1, 5 * bb, bb, 1),
           (True, 0, 0, 3 * bb, 2), (False, 0, bb, bb, 3)]
    keys = [data.volume_key(seed, v) for v in (0, 1)]
    image = [data.prefill_bytes_np(k, np.arange(n_blocks * bb))
             .reshape(n_blocks, bb) for k in keys]
    image[0][0:2] = pay.rows(0, 2)
    image[1][5] = pay.rows(1, 1)[0]
    read = image[0][0:3].tobytes()
    image[0][1] = pay.rows(3, 1)[0]
    sampled = {2: read}
    return reference.Replay(log, bb, sampled), sampled, pay.pool, keys, image


def test_reference_catches_a_corrupted_read():
    replay, sampled, pool, keys, _ = _replay_and_image()
    assert reference.read_mismatch_bytes(replay, sampled, pool, keys) == (0,
                                                                          1)
    bad = bytearray(sampled[2])
    bad[100] ^= 1
    assert reference.read_mismatch_bytes(replay, {2: bytes(bad)}, pool,
                                         keys) == (1, 1)
    assert reference.read_mismatch_bytes(replay, {2: sampled[2][:-1]}, pool,
                                         keys)[0] == len(sampled[2])


def test_reference_catches_a_corrupted_replica_row():
    import jax.numpy as jnp
    bb, pb, n_pages = 64, 8, 4
    replay, _, pool, keys, image = _replay_and_image(bb=bb,
                                                     n_blocks=n_pages * pb)
    # two replicas holding the image, extents laid out volume-major behind
    # a permuted extent map (row E is the dump row)
    perm = np.random.default_rng(0).permutation(2 * n_pages)
    table = np.full((1, 2, n_pages), -1, np.int32)
    pools = np.zeros((1, 2 * n_pages + 1, pb, bb), np.float32)
    for v in range(2):
        for p in range(n_pages):
            table[0, v, p] = perm[v * n_pages + p]
            pools[0, perm[v * n_pages + p]] = image[v][p * pb:(p + 1) * pb]
    good = jnp.asarray(pools)
    broken = pools.copy()
    broken[0, table[0, 1, 2], 3, 7] += 1          # one byte of replica 1
    holed = table.copy()
    holed[0, 0, 1] = -1                           # a page lost
    bad, holes = reference.replica_mismatch_bytes(
        [good, jnp.asarray(broken), good],
        [jnp.asarray(table), jnp.asarray(table), jnp.asarray(holed)],
        [0, 1], keys, replay, pool, n_pages, pb)
    assert bad[:2] == [0, 1] and holes == [0, 0, 1]
    assert bad[2] == pb * bb                      # the whole lost page
