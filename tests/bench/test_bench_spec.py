"""BENCHMARK.json against the benchmark's contract, and resolution by name."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

from bench import flops, harness, model  # noqa: E402
import tiny  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|_dim$|_rank$|"
                   r"expansion|experts_per_tok|kv_channels|ffn)", re.I)


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/")
        assert ".." not in p.split("/") and (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(LINE.match(w) for w in cmd)
    for word in cmd[1:]:
        if (ROOT / word).exists():
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_lines():
    entries = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)
    for c in SPEC["configs"]:
        assert LINE.match(c["source"]) and LINE.match(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        assert LINE.match(m["layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_entry_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")


def test_every_cell_reports_what_the_contract_asks():
    bench = harness.Bench()
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for w in SPEC["workloads"]:
        reported = {m["name"] for m in bench.end_to_end(w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert bench.per_layer(w["name"]), w["name"]
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert m["moves"] in {x["name"] for x in bench.end_to_end(cell)}
    for m in SPEC["per_layer"]:
        if harness.quantity(m["name"]).endswith("_roofline"):
            assert m["unit"] == "%"
            mfu = [x for x in SPEC["per_layer"] if "mfu" in x["name"]
                   and x["moves"] == m["moves"]]
            covered = {c for x in mfu for c in x["workloads"]}
            assert set(m["workloads"]) <= covered, m["name"]


def test_split_metrics_read_one_quantity():
    """A name split by a dot reads its quantity: an end-to-end metric the
    harness computes, a per-layer metric the quantity's reader; the readers
    of ``mfu.decode`` and ``mfu.handoff`` are one file."""
    for m in SPEC["end_to_end"]:
        assert harness.quantity(m["name"]) in {"setup_s", "tokens_per_s",
                                               "latency_p50_s", "latency_p95_s"}
    assert harness.quantity("latency_p50_s.handoff") == "latency_p50_s"
    assert (harness.metric_reader("mfu.decode").__file__
            == harness.metric_reader("mfu.handoff").__file__)
    assert Path(harness.metric_reader("mfu.decode").__file__).name == "mfu.py"
    with pytest.raises(KeyError):
        harness.metric_reader("no_such_quantity.decode")


def test_configs_files_and_reductions():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["bench"]["source"] == c["source"]
        assert cfg["bench"]["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert not WIDTH.search(k), f"{k} is a width"
            assert k in cfg


@pytest.mark.parametrize("name", sorted(f.stem for f in (ROOT / "bench" / "configs").glob("*.json")))
def test_program_runs_what_the_file_states(name):
    """Every size of the file reaches the program's ModelConfig, and the
    benchmark's parameter count equals the program's parameter tree."""
    config = harness.Bench().config(name)
    c, cfg = model.canonical(config), model.program_config(config)
    for k in model.CANONICAL:
        assert getattr(cfg, k) == c[k], k
    shapes = model.weight_shapes(cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == flops.param_count(c)


def test_resolution_by_name():
    bench = harness.Bench()
    for w in SPEC["workloads"]:
        assert bench.cell(w["name"]) == w
        bench.config(w["config"])
        mix = bench.mix(w["traffic"])
        harness.driver(mix["driver"])
        assert "max_gap" in bench.limit(w["name"])
    for m in SPEC["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
    for lookup in (bench.cell, bench.config, bench.mix, bench.limit,
                   harness.driver, harness.metric_reader):
        with pytest.raises(KeyError):
            lookup("no-such-name")


def test_a_new_mix_needs_only_new_files(tmp_path):
    """A cell whose mix exists only in a new data file resolves by name."""
    root = tiny.tiny_root(tmp_path, configs=("qwen3-4b",), mixes=("decode",))
    mix = dict(tiny.tiny_mix("decode"), prompt_len=20, why="a new mix")
    (root / "bench" / "mixes" / "longer.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "qwen3-4b.longer", "config": "qwen3-4b",
                              "traffic": "longer", "chips": 1, "why": "new"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = harness.Bench(root)
    cell = bench.cell("qwen3-4b.longer")
    assert bench.mix(cell["traffic"])["prompt_len"] == 20
    assert harness.driver(bench.mix(cell["traffic"])["driver"]).cycle


def test_peaks_lookup():
    row = harness.peak_row("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peak_row("TPU v99")


def test_run_refuses_the_cpu(tmp_path):
    """bench/run.py on a CPU: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", SPEC["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr
