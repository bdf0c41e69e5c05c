"""What the benchmark reads of the program: the trace reduction with the
program's own ``truffle.*`` spans nested inside the harness's ``bench.*``
spans, and the names of the compiled programs the roofline readers match."""
from __future__ import annotations

import re
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import harness, trace  # noqa: E402

MS = 1_000_000


def _trace(program_spans: bool):
    """The device idles over [15, 30] and [40, 90] ms of a 100 ms window.
    The program's spans split the host's ``bench.step_batch`` and
    ``bench.serialize`` time into its own phases."""
    host = [["bench.traced", 0, 100 * MS],
            ["bench.step_batch", 0, 44 * MS],
            ["bench.serialize", 45 * MS, 30 * MS]]
    if program_spans:
        host += [["truffle.engine.batch", 1 * MS, 42 * MS],
                 ["truffle.engine.sample", 15 * MS, 5 * MS],
                 ["truffle.engine.token_sync", 20 * MS, 10 * MS],
                 ["truffle.csp.serialize", 46 * MS, 28 * MS],
                 ["truffle.csp.d2h", 46 * MS, 9 * MS],
                 ["truffle.csp.pack", 60 * MS, 14 * MS]]
    return {"devices": [{"name": "/device:TPU:0",
                         "ops": [["a", 0, 15 * MS], ["b", 30 * MS, 10 * MS],
                                 ["c", 90 * MS, 10 * MS]],
                         "modules": [["jit_prefill", 0, 15 * MS],
                                     ["jit__lambda", 30 * MS, 10 * MS],
                                     ["jit__lambda", 90 * MS, 10 * MS]]}],
            "host": host}


@pytest.mark.parametrize("program_spans, expected", [
    (False, {"bench.step_batch": 19, "bench.serialize": 30,
             "outside any bench span": 16}),
    (True, {"truffle.engine.sample": 5, "truffle.engine.token_sync": 10,
            "truffle.engine.batch": 3, "bench.step_batch": 1,
            "bench.serialize": 2, "truffle.csp.d2h": 9,
            "truffle.csp.serialize": 5, "truffle.csp.pack": 14,
            "outside any bench span": 16}),
])
def test_idle_goes_to_the_innermost_span(program_spans, expected):
    s = trace.reduce(_trace(program_spans))
    assert {n: pytest.approx(ms / 1000) for n, ms in expected.items()} == dict(
        s.idle_gaps)
    assert sum(dict(s.idle_gaps).values()) == pytest.approx(s.window_s - s.busy_s)


def test_program_spans_leave_the_device_numbers_alone():
    with_spans, without = trace.reduce(_trace(True)), trace.reduce(_trace(False))
    for field in ("window_s", "busy_s", "modules", "device_ops"):
        assert getattr(with_spans, field) == getattr(without, field), field


def test_warmup_programs_carry_the_roofline_readers_module_names():
    """``prefill_roofline`` and ``decode_roofline`` find their programs by the
    XLA module names ``ServeEngine.warmup`` gives them; a rename would read
    as no such module and the metric would fall silent."""
    from repro.configs.registry import get_config
    from repro.models import api
    from repro.serving.engine import ServeEngine

    cfg = get_config("qwen3-4b", smoke=True)
    eng = ServeEngine(cfg, api.init(cfg, jax.random.PRNGKey(0)), max_batch=2,
                      max_len=12)
    eng.warmup(8)
    for program, reader in ((eng._prefill, "prefill_roofline"),
                            (eng._decode, "decode_roofline")):
        module = re.match(r"HloModule (\S+?),", program.as_text()).group(1)
        prefix = harness.metric_reader(reader).MODULE
        assert module.startswith(prefix), (module, prefix)
    assert harness.metric_reader("prefill_roofline").MODULE == "jit_prefill"
    assert harness.metric_reader("decode_roofline").MODULE == "jit__lambda"
