"""BENCHMARK.json keeps to its contract, and the harness finds every
configuration, traffic mix, cell file and per-layer reader by name."""
import json
import re
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT
BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for e in BENCH[group]:
            assert set(e) - {"workloads"} == want, e["name"]
            assert NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                                 "higher")
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    e2e = {e["name"] for e in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_found_by_name(w):
    cell = spec.cell(w["name"], BENCH)
    assert w["chips"] in (1, 4)
    assert cell["config"]["name"] == w["config"]
    assert cell["traffic"]["arrival"]["process"] == "poisson"
    limits = cell["cell"]["check"]["limits"]
    assert set(limits) == {"kv_differ_share", "served_logit_gap"}
    assert all(v > 0 for v in limits.values())
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert c["file"].startswith("bench/configs/")
    assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    pub, arch = cfg["published"], cfg["arch"]
    assert arch["n_layers"] == pub["num_hidden_layers"]
    assert arch["d_model"] == pub["hidden_size"]
    assert arch["n_heads"] == pub["num_attention_heads"]
    assert arch["n_kv_heads"] == pub["num_key_value_heads"]
    assert arch["d_ff"] == pub["intermediate_size"]
    assert arch["vocab"] == pub["vocab_size"]
    assert arch["window"] == (pub.get("sliding_window") or 0)
    eng = cfg["engine"]
    assert eng["max_len"] % eng["page_len"] == 0


def test_no_chip_no_result(capsys, monkeypatch):
    from bench import run
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    rc = run.main(["--workload", BENCH["workloads"][0]["name"], "--seed",
                   str(2**31 + 7), "--seconds", "1", "--trace", "0"])
    assert rc == 2 and capsys.readouterr().out == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, *BENCH["command"][1:],
                        "--workload", BENCH["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120, env={"PATH": "/usr/bin:/bin",
                                         "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
