"""BENCHMARK.json against the benchmark's contract, and resolution by name."""
from __future__ import annotations

import functools
import hashlib
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

from bench import harness, model  # noqa: E402
import tiny  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
# The weights and work counts the existing cells were measured and their
# limits calibrated with (``data/yardstick_before_files.json``).
BEFORE = json.loads((ROOT / "tests" / "bench" / "data" /
                     "yardstick_before_files.json").read_text())
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


def _program_value(cfg, key: str):
    """The program's value of a size name; a dotted one is a nested field's."""
    return functools.reduce(getattr, key.split("."), cfg)


@pytest.mark.parametrize("name", sorted(f.stem for f in (ROOT / "bench" / "configs").glob("*.json")))
def test_program_runs_what_the_file_states(name):
    """Every size the file states, by its reference's ``KEYS``, reaches the
    program's ModelConfig, and its work count's parameter count equals the
    program's parameter tree."""
    bench = harness.Bench()
    config = bench.config(name)
    keys = bench.reference(config).KEYS
    c = model.canonical(config, keys)
    cfg = model.program_config(config, c)
    for k in keys:
        assert _program_value(cfg, k) == c[k], k
    shapes = model.weight_shapes(cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == bench.work(config).param_count(c)


@pytest.mark.parametrize("name", sorted(BEFORE["weights_sha256"]))
def test_weights_are_bitwise_the_calibrated_ones(name):
    """The weights of a tiny copy of each configuration, drawn by the
    program's parameter definitions, are bit for bit the ones the limits
    were calibrated with."""
    config = tiny.tiny_config(name)
    cfg = model.program_config(config, harness.Bench().canonical(config))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(model.make_weights(cfg, BEFORE["seed"])):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == BEFORE["weights_sha256"][name]


@pytest.mark.parametrize("cell", sorted(BEFORE["work"]))
def test_work_counts_are_the_calibrated_ones(cell):
    """Each cell's operations and bytes at its mix's shapes, by its
    configuration's work module, equal exactly those its rooflines and
    ``mfu`` were first read with."""
    bench = harness.Bench()
    w = bench.cell(cell)
    config = bench.config(w["config"])
    c, work = bench.canonical(config), bench.work(config)
    pf, steps = harness.cycle_work(work, c, bench.mix(w["traffic"]))
    want = BEFORE["work"][cell]
    assert list(pf) == want["prefill"]
    assert [list(s) for s in steps] == want["decode_steps"]
    assert work.param_count(c) == want["param_count"]
    assert work.kv_bytes_per_token(c) == want["kv_bytes_per_token"]


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


# Two architectures of the program's registry that no benchmark file covers,
# at smoke widths: latent attention (MLA), and experts with shared ones.
COMMON_KEYS = {"num_layers": ("num_hidden_layers", 2), "d_model": ("hidden_size", 64),
               "num_heads": ("num_attention_heads", 4),
               "num_kv_heads": ("num_key_value_heads", 4), "head_dim": ("head_dim", 16),
               "d_ff": ("intermediate_size", 128), "vocab_size": ("vocab_size", 512),
               "norm_eps": ("rms_norm_eps", 1e-6)}
NEW_ARCHITECTURES = {
    "minicpm3-4b": ("mla_stub", {
        "mla.q_lora_rank": ("q_lora_rank", 32), "mla.kv_lora_rank": ("kv_lora_rank", 16),
        "mla.qk_nope_head_dim": ("qk_nope_head_dim", 16),
        "mla.qk_rope_head_dim": ("qk_rope_head_dim", 8),
        "mla.v_head_dim": ("v_head_dim", 16)},
        {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"}),
    "qwen2-moe-a2.7b": ("moe_stub", {
        "moe.num_experts": ("num_experts", 6), "moe.top_k": ("num_experts_per_tok", 2),
        "moe.d_expert": ("moe_intermediate_size", 64),
        "moe.num_shared": ("num_shared_experts", 2), "qkv_bias": ("attention_bias", True)},
        {"router", "w_gate", "w_up", "w_down", "shared"}),
}
STUB_WORK = """
def param_count(c):
    return 7


def kv_bytes_per_token(c):
    return 3


def prefill(c, batch, seq):
    return float(batch * seq * c["d_model"]), 11.0


def decode_step(c, batch, kv_len):
    return float(batch * kv_len), 13.0
"""


def _tree_files(path: Path):
    return sorted((str(f.relative_to(path)), f.stat().st_mtime_ns)
                  for f in path.rglob("*") if "__pycache__" not in f.parts)


@pytest.mark.parametrize("arch", sorted(NEW_ARCHITECTURES))
def test_a_new_architecture_needs_only_new_files(tmp_path, arch):
    """A configuration of another architecture enters with new files alone:
    its config, a reference that declares the sizes it takes (dotted ones
    reach the program's nested MLAConfig or MoEConfig), and a work count.
    Every size reaches the program, every leaf gets a weight, and the cell's
    work comes from the new module; nothing under ``bench/`` is written."""
    before = _tree_files(ROOT / "bench")
    reference, own_keys, leaves = NEW_ARCHITECTURES[arch]
    keys = {**COMMON_KEYS, **own_keys}
    root = tiny.tiny_root(tmp_path, configs=("qwen3-4b",), mixes=("decode",))
    config = {published: value for published, value in keys.values()}
    config.update(torch_dtype="bfloat16", bench={
        "source": "smoke widths", "arch": arch, "reference": reference,
        "keys": {k: published for k, (published, _) in keys.items()},
        "architecture": {"why": "none"}})
    (root / "bench" / "configs" / f"{arch}.json").write_text(json.dumps(config))
    (root / "bench" / "reference" / f"{reference}.py").write_text(
        f"KEYS = {tuple(keys)!r}\n")
    (root / "bench" / "work" / f"{reference}.py").write_text(STUB_WORK)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": f"{arch}.decode", "config": arch,
                              "traffic": "decode", "chips": 1, "why": "new"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = harness.Bench(root)
    cell = bench.cell(f"{arch}.decode")
    config = bench.config(cell["config"])
    c = bench.canonical(config)
    cfg = model.program_config(config, c)
    for k, (_, value) in keys.items():
        assert _program_value(cfg, k) == value, k
    weights = model.make_weights(cfg, 2**33 + 3)
    shapes = model.weight_shapes(cfg)
    assert jax.tree.structure(weights) == jax.tree.structure(shapes)
    for w, sh in zip(jax.tree.leaves(weights), jax.tree.leaves(shapes)):
        assert (w.shape, w.dtype) == (sh.shape, sh.dtype)
        assert np.isfinite(np.asarray(w, np.float32)).all()
    block = weights["blocks"]["pos0"]
    assert leaves <= set(block["mixer"]) | set(block["mlp"])
    mix = bench.mix(cell["traffic"])
    pf, steps = harness.cycle_work(bench.work(config), c, mix)
    B, L = mix["clients"], mix["prompt_len"]
    assert pf == (float(B * L * 64), 11.0)
    assert steps == [(float(B * (L + i + 1)), 13.0) for i in range(mix["new_tokens"] - 1)]
    assert _tree_files(ROOT / "bench") == before


def test_sizes_are_checked_against_the_reference():
    """A file that lacks a size its reference takes, or states one it does
    not take, is refused; so is a size the program has no field for."""
    bench = harness.Bench()
    config = bench.config("qwen3-4b")
    keys = bench.reference(config).KEYS
    with pytest.raises(ValueError, match="lacks"):
        model.canonical(config, keys + ("mla.kv_lora_rank",))
    with pytest.raises(ValueError, match="does not take"):
        model.canonical(config, keys[1:])
    c = model.canonical(config, keys)
    for bad in ("no_such_size", "mla.kv_lora_rank", "num_layers.x"):
        with pytest.raises(ValueError, match="no field"):
            model.program_config(config, dict(c, **{bad: 1}))


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
