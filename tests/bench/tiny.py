"""Tiny copies of the benchmark's configurations and mixes, for CPU tests.

``tiny_root(tmp)`` writes a root that ``bench.harness.Bench`` reads: a
BENCHMARK.json with one cell per (configuration, mix), each configuration
cut to smoke widths through the published keys its file maps, each mix cut
to a few short requests, and copies of the references and work counts."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SMOKE = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512}
MIXES = {"decode": {"clients": 4, "prompt_len": 12, "new_tokens": 24,
                    "check_requests": 4},
         "handoff": {"clients": 1, "prompt_len": 24, "new_tokens": 8,
                     "check_requests": 8}}
# The limit on the widest served-token gap at these sizes, set as on the chip
# from two readings (CPU, six seeds each, decode mix, 96 positions): sound
# bf16 runs read at most 0.029 logits, the float8 control at least 0.168.
# The handoff mix checks 64 positions (8 requests of 8 tokens): at 12 the
# control read under the limit on some seeds.
TINY_LIMIT = 0.1


def tiny_config(name: str) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    for k, v in SMOKE.items():
        cfg[cfg["bench"]["keys"][k]] = v
    return cfg


def tiny_mix(name: str) -> dict:
    mix = json.loads((ROOT / "bench" / "mixes" / f"{name}.json").read_text())
    mix.update(MIXES[name])
    return mix


def tiny_root(tmp: Path, configs=("qwen3-4b", "glm4-9b-20l"),
              mixes=("decode", "handoff"), limit: float = TINY_LIMIT) -> Path:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for kind in ("configs", "mixes", "limits"):
        (tmp / "bench" / kind).mkdir(parents=True, exist_ok=True)
    for kind in ("reference", "work"):
        shutil.copytree(ROOT / "bench" / kind, tmp / "bench" / kind,
                        ignore=shutil.ignore_patterns("__pycache__"),
                        dirs_exist_ok=True)
    cells = []
    for c in configs:
        (tmp / "bench" / "configs" / f"{c}.json").write_text(json.dumps(tiny_config(c)))
        for m in mixes:
            name = f"{c}.{m}"
            cells.append({"name": name, "config": c, "traffic": m, "chips": 1,
                          "why": "tiny"})
            (tmp / "bench" / "limits" / f"{name}.json").write_text(
                json.dumps({"max_gap": limit}))
    for m in mixes:
        (tmp / "bench" / "mixes" / f"{m}.json").write_text(json.dumps(tiny_mix(m)))
    names = [w["name"] for w in cells]
    spec["workloads"] = cells
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:             # every tiny cell of the same mixes
            m["workloads"] = [n for n in names if any(
                n.endswith("." + w.split(".")[-1]) for w in m["workloads"])]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
