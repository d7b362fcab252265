"""Discovery by name, and BENCHMARK.json against the files it names."""
import os
import subprocess
import sys

import pytest

from bench import reference, registry

BENCH = registry.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_cells_find_their_files(name):
    cell = registry.cell(name)
    assert cell.traffic["rate_per_s"] > 0
    d = reference.dims_of(cell.config)
    assert d.d_model % d.heads == 0 or d.head_dim
    assert cell.config["serving"]["chips"] == cell.chips
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "itl_p50_ms"}
    assert cell.per_layer


@pytest.mark.parametrize("name", CELLS)
def test_the_rate_is_the_cells_and_the_mix_is_shared(name):
    w = {w["name"]: w for w in BENCH["workloads"]}[name]
    assert "rate_per_s" not in registry.traffic(w["traffic"])
    cell = registry.cell(name)
    assert cell.traffic == dict(registry.traffic(w["traffic"]),
                                **registry._data("cells", name))


@pytest.mark.parametrize("c", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_every_reduced_key_is_a_stated_departure(c):
    """A key changed from the published config is in ``reduced`` and says,
    in the file, what was published and what runs."""
    import json
    with open(os.path.join(registry.ROOT, c["file"])) as f:
        conf = json.load(f)
    assert set(c["reduced"]) <= set(conf["departures"])
    for key in c["reduced"]:
        assert key in conf


def test_unknown_cell():
    with pytest.raises(KeyError, match="no workload"):
        registry.cell("no-such.cell")


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_readers_declare_what_the_benchmark_says(m):
    mod = registry.reader(m["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (m["unit"], m["layer"],
                                                m["moves"])
    assert callable(mod.read)
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_every_config_is_used_and_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert os.path.isfile(os.path.join(registry.ROOT, c["file"]))


@pytest.mark.parametrize("name", CELLS)
def test_program_config_matches_the_file(name):
    from bench import system
    cell = registry.cell(name)
    d = reference.dims_of(cell.config)
    system.check_norm_eps(d)
    cfg = system.model_config(cell.config_name, d, "bfloat16")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        d.layers, d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff,
        d.vocab)


def test_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(registry.BENCH_DIR, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=registry.ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_benchmark_json_shape():
    import re
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for sec, want in keys.items():
        for e in BENCH[sec]:
            assert set(e) - {"workloads"} == want, e
            assert name.match(e["name"])
            for k in ("why", "layer", "source"):
                assert k not in e or 1 <= len(e[k]) <= 200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= BENCH["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
