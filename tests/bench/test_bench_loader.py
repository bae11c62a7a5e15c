"""Configurations, traffic mixes and metric readers are found by name;
unknown names are refused; a mix added as a file alone is picked up and
runs; ``BENCHMARK.json`` keeps the format the benchmark requires."""
import json
import re
import shutil

import bench_tiny
import pytest

from bench import loader
from bench.traffic import Generator, Mix

BENCH = loader.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", bench_tiny.CELLS)
def test_every_cell_loads_by_name(name):
    cell = loader.load_cell(name)
    assert cell.chips == 1
    assert cell.config["name"] == cell.config_name
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert "setup_s" in {m["name"] for m in cell.end_to_end}


def test_cells_keep_their_order():
    assert tuple(w["name"] for w in BENCH["workloads"]) == bench_tiny.CELLS


@pytest.mark.parametrize("kind,call", [
    ("workload", lambda: loader.load_cell("no-such-cell")),
    ("config", lambda: loader.load_config("no-such-config")),
    ("traffic", lambda: loader.load_mix("no-such-mix")),
    ("metric", lambda: loader.load_reader("no_such_metric")),
    ("config", lambda: loader.load_config("../BENCHMARK")),
])
def test_unknown_names_are_refused(kind, call):
    with pytest.raises(ValueError, match=kind):
        call()


def test_bad_mix_parameters_are_refused():
    good = {"qd": 1, "call_bytes": 4096, "read_share": 0.5,
            "pattern": "random"}
    Mix.from_spec("m", good)
    for bad in ({"qd": 0}, {"read_share": 1.5}, {"pattern": "zipf"}):
        with pytest.raises(ValueError):
            Mix.from_spec("m", dict(good, **bad))
    mix = Mix.from_spec("m", dict(good, call_bytes=1000))
    with pytest.raises(ValueError, match="whole number"):
        Generator(mix, seed=1, n_volumes=1, volume_bytes=1 << 20,
                  block_bytes=4096)


def test_generator_is_a_function_of_the_seed():
    mix = Mix.from_spec("m", {"qd": 4, "call_bytes": 8192,
                              "read_share": 0.5, "pattern": "random"})
    big_seed = 2 ** 31 + 977

    def draw(seed):
        g = Generator(mix, seed=seed, n_volumes=4, volume_bytes=1 << 24,
                      block_bytes=4096)
        return [g.next() for _ in range(50_000)]
    a, b = draw(big_seed), draw(big_seed)
    assert a == b
    assert a != draw(big_seed + 1)
    assert all(o % 8192 == 0 and o + n <= 1 << 24 for _, _, o, n in a)
    reads = sum(r for r, *_ in a) / len(a)
    assert 0.48 < reads < 0.52


def test_sequential_cursors_take_volumes_in_turn_and_wrap():
    mix = Mix.from_spec("m", {"qd": 2, "call_bytes": 4096,
                              "read_share": 0.0, "pattern": "sequential"})
    g = Generator(mix, seed=3, n_volumes=2, volume_bytes=4 * 4096,
                  block_bytes=4096)
    calls = [g.next() for _ in range(10)]
    assert [v for _, v, _, _ in calls] == [0, 1] * 5
    offs0 = [o for _, v, o, _ in calls if v == 0]
    assert [(b - a) % (4 * 4096) for a, b in zip(offs0, offs0[1:])] \
        == [4096] * 4


def test_a_mix_added_as_a_file_alone_is_picked_up_and_runs(tmp_path):
    """A later PR adds a traffic mix as a data file and a cell entry; the
    harness needs no edit."""
    shutil.copytree(loader.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "traffic" / "randrw8k-qd4.json").write_text(
        json.dumps({"source": "fio rw=randrw bs=8k iodepth=4",
                    "loop": "closed", "qd": 4, "call_bytes": 8192,
                    "read_share": 0.7, "pattern": "random"}))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "randrw8k-qd4.3r",
                               "config": "longhorn-3r",
                               "traffic": "randrw8k-qd4", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("iops", "read_p99_ms", "write_p99_ms",
                         "api_us_per_call"):
            m["workloads"].append("randrw8k-qd4.3r")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = bench_tiny.tiny_cell("randrw8k-qd4.3r", tmp_path / "bench")
    assert cell.mix.read_share == 0.7 and cell.mix.qd == 4
    out = bench_tiny.run_tiny("randrw8k-qd4.3r", cell=cell, seconds=0.2)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"iops", "read_p99_ms", "write_p99_ms",
                                   "setup_s"}


def test_benchmark_json_keeps_its_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert loader.load_config(c["name"])["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] == 1
        assert len(w["why"]) <= 200
        reported = [m for m in e2e.values()
                    if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in BENCH["per_layer"])
    names = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert all(c in cells for c in m.get("workloads", []))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] == 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        assert all(c in moved.get("workloads", cells) for c in m["workloads"])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    assert len(json.dumps(BENCH)) < 64 * 1024
