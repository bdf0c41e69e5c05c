"""ML integration (beyond-paper): Truffle's SDP applied to a training job's
cold start. η = REAL XLA compile of the train step; δ = first batches +
checkpoint streaming from throttled storage. Baseline runs the lifecycle
sequentially; Truffle overlaps — time-to-first-step is the metric."""
from __future__ import annotations

import threading
import time

import benchmarks.common  # noqa: F401  (sys.path side effect)
import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config
from repro.data.pipeline import TokenDataset, TruffleDataLoader
from repro.launch.mesh import host_device_mesh
from repro.launch.steps import build_train_step, concrete_train_state
from repro.distributed.sharding import rules_for_shape
from repro.runtime.clock import Clock
from repro.runtime.netsim import GBPS
from repro.storage.base import StorageService


def _one_run(overlap: bool, *, provision_s: float = 1.0) -> float:
    cfg = get_config("qwen3-4b", smoke=True)
    shape = ShapeConfig("bench", 256, 8, "train")
    mesh = host_device_mesh(1, 1)
    clock = Clock(1.0)
    # slow-ish object store so δ is material (~1.5 s for 2 batches)
    storage = StorageService("s3", put_bandwidth=10 * GBPS,
                             get_bandwidth=0.05 * GBPS, latency=0.03,
                             clock=clock)
    ds = TokenDataset(cfg.vocab_size, shape.seq_len, shape.global_batch)
    loader = TruffleDataLoader(ds, storage, prefetch_depth=2, populate=2)
    train_step, (state_sds, batch_sds) = build_train_step(cfg, mesh, shape)

    t0 = time.monotonic()
    box = {}

    def cold():
        clock.sleep(provision_s)                       # ν (simulated)
        with jax.set_mesh(mesh):
            box["exe"] = jax.jit(train_step).lower(state_sds, batch_sds).compile()

    if overlap:                                        # Truffle path
        th = threading.Thread(target=cold)
        th.start()
        loader.start_prefetch()                        # SDP during cold start
        th.join()
    else:                                              # sequential lifecycle
        cold()
        loader.start_prefetch()

    with jax.set_mesh(mesh):
        state = concrete_train_state(cfg, mesh, rules_for_shape("train"),
                                     jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in loader.get(0).items()}
    state, metrics = box["exe"](state, batch)
    float(metrics["loss"])
    loader.stop()
    return time.monotonic() - t0


def run():
    base = _one_run(overlap=False)
    truf = _one_run(overlap=True)
    imp = 1 - truf / base
    rows = [("train.time_to_first_step.baseline", base, "sequential lifecycle"),
            ("train.time_to_first_step.truffle", truf,
             f"compile||prefetch overlap improvement={imp:.0%}")]
    emit(rows)
    return rows


if __name__ == "__main__":
    run()
