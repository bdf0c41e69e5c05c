"""Registry + config integrity: all 11 archs, param counts vs published
sizes, reduced smoke configs."""
import pytest

from repro.configs.registry import get_config, list_archs

PUBLISHED_B = {  # (total, active) billions from the papers / model cards
    "glm4-9b": (9.4, 9.4),
    "minicpm3-4b": (4.1, 4.1),
    "qwen3-4b": (4.0, 4.0),
    "stablelm-1.6b": (1.6, 1.6),
    "jamba-v0.1-52b": (52.0, 12.0),
    "olmoe-1b-7b": (6.9, 1.3),
    "qwen2-moe-a2.7b": (14.3, 2.7),
    "whisper-medium": (0.77, 0.77),
    "xlstm-125m": (0.16, 0.16),
    "qwen2-vl-7b": (7.6, 7.6),
    "deepseek-v2-lite": (15.7, 2.4),
}


def test_all_archs_present():
    assert len(list_archs()) == 11
    assert set(list_archs()) == set(PUBLISHED_B)


@pytest.mark.parametrize("arch", list(PUBLISHED_B))
def test_param_count_matches_published(arch):
    cfg = get_config(arch)
    total, active = PUBLISHED_B[arch]
    assert cfg.param_count() / 1e9 == pytest.approx(total, rel=0.15)
    assert cfg.param_count(active_only=True) / 1e9 == pytest.approx(active, rel=0.15)


@pytest.mark.parametrize("arch", list(PUBLISHED_B))
def test_smoke_config_valid(arch):
    cfg = get_config(arch, smoke=True)
    assert cfg.num_periods >= 1
    assert cfg.d_model <= 128  # genuinely reduced

