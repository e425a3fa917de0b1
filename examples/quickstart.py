"""Quickstart: concurrent stateful stream processing in 40 lines.

Defines a tiny word-count-style application over shared state, runs it
through TStream's dual-mode engine, and checks the result against the
sequential oracle.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import setup_compile_cache
from repro.core import AppSpec, DualModeEngine, EngineConfig, make_store
from repro.core.types import ASSOC_FUNS

N_KEYS = 100


def make_app():
    def state_access(blt, eb):
        # one transaction: bump the key's counter, read it back
        blt.read_modify(0, eb["key"], eb["amount"], "add")
        blt.read(0, eb["key"])

    return AppSpec(
        name="counter", funs=ASSOC_FUNS, max_ops=2, width=1,
        make_store=lambda **_: make_store([N_KEYS], 1),
        gen_events=lambda rng, n: dict(
            key=rng.integers(0, N_KEYS, n).astype(np.int32),
            amount=rng.uniform(0, 10, n).astype(np.float32)),
        pre_process=lambda ev: ev,
        state_access=state_access,
        post_process=lambda eb, res: dict(count_after=res.pre[1, 0]),
    )


def main():
    setup_compile_cache()
    app = make_app()
    store = app.make_store()
    rng = np.random.default_rng(0)
    stream = app.gen_events(rng, 256)

    engine = DualModeEngine(app, store, EngineConfig(scheme="tstream"))
    outs, values = engine.run_stream(store.values, stream,
                                     punct_interval=64)

    oracle = DualModeEngine(app, store, EngineConfig(scheme="lock"))
    outs_o, values_o = oracle.run_stream(store.values, stream,
                                         punct_interval=64)
    np.testing.assert_allclose(np.asarray(values), np.asarray(values_o),
                               rtol=1e-5)
    total = float(np.asarray(values)[:N_KEYS].sum())
    print(f"quickstart OK — {len(outs)} punctuation intervals, "
          f"total count {total:.1f}, matches oracle ✓")


if __name__ == "__main__":
    main()
