"""The harness finds configurations, mixes, drivers and metrics by the
names BENCHMARK.json gives, and refuses anything else."""
import json
import re

import pytest

from bench.harness import (ROOT, UnknownName, load_benchmark, load_json,
                           load_module, prng_key, resolve)

SPEC = load_benchmark()
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("tiny", [False, True])
def test_every_cell_resolves(cell, tiny):
    c = resolve(cell, tiny=tiny)
    assert c.size.grid * c.size.grid == c.chips
    assert c.size.n % (c.size.b * c.size.grid) == 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert names - {"setup_s"} <= set(c.driver.end_to_end(c, 3, 2.0))
    assert c.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(load_module("metrics", metric).compute)


@pytest.mark.parametrize("kind,name", [
    ("configs", "no-such-config"), ("mixes", "n1.grid9"),
    ("metrics", "no_such_metric"), ("drivers", "linpack"),
    ("configs", "../BENCHMARK"), ("metrics", "a/b"), ("mixes", "")])
def test_unknown_names_are_refused(kind, name):
    load = load_module if kind in ("metrics", "drivers") else load_json
    with pytest.raises(UnknownName):
        load(kind, name)


def test_unknown_workload_is_refused():
    with pytest.raises(UnknownName):
        resolve("hpl.n99.1chip")


def test_benchmark_json_keeps_to_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench", "tests/bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = set(CELLS)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for w in m["workloads"]:
            moved = next(x for x in SPEC["end_to_end"]
                         if x["name"] == m["moves"])
            assert w in moved.get("workloads", CELLS)
    names = ([c["name"] for c in SPEC["configs"]] + CELLS +
             [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_seeds_wider_than_32_bits_give_distinct_keys():
    import jax
    keys = [jax.random.key_data(prng_key(s)).tolist()
            for s in (7, 7 + 2 ** 32, 2 ** 31 + 5, 2 ** 33 + 7)]
    assert len({tuple(k) for k in keys}) == 4
    with pytest.raises(ValueError):
        prng_key(-1)


def _spec_with(**change):
    spec = load_benchmark()
    cell = dict(spec["workloads"][0], **change)
    return dict(spec, workloads=[cell]), cell["name"]


def test_a_mix_the_driver_cannot_run_is_refused():
    spec, name = _spec_with(chips=4)        # a 1x1 mix on four chips
    with pytest.raises(ValueError, match="grid"):
        resolve(name, spec=spec)


def test_an_end_to_end_metric_no_driver_gives_is_refused():
    spec, name = _spec_with()
    spec["end_to_end"] = spec["end_to_end"] + [
        {"name": "beff_bytes_per_s", "unit": "B/s", "better": "higher",
         "bound": 0.05, "source": "host_clock"}]
    with pytest.raises(UnknownName, match="beff_bytes_per_s"):
        resolve(name, spec=spec)
