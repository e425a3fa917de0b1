"""Toll Processing end-to-end (the paper's motivating application, Fig 2b).

Streams Linear-Road position reports through the fused RS/VC/TN operator
with concurrent shared state, comparing all consistency-preserving engines.

    PYTHONPATH=src python examples/toll_processing.py
"""
import time

import numpy as np

from repro.apps import TP
from repro.compile_cache import setup_compile_cache
from repro.core import DualModeEngine, EngineConfig


def main():
    setup_compile_cache()
    rng = np.random.default_rng(42)
    stream = TP.gen_events(rng, 2000)
    store = TP.make_store()

    results = {}
    for scheme in ["tstream", "lock", "pat"]:
        eng = DualModeEngine(TP, store, EngineConfig(scheme=scheme))
        t0 = time.time()
        outs, values = eng.run_stream(store.values, stream,
                                      punct_interval=500)
        dt = time.time() - t0
        tolls = np.concatenate([np.asarray(o["toll"]) for o in outs])
        results[scheme] = (values, tolls, dt)
        print(f"[tp] {scheme:8s}: {len(tolls)} tolls in {dt:.2f}s, "
              f"mean toll {tolls.mean():.3f}, "
              f"congested events {(tolls > 0).sum()}")

    v_t, tolls_t, _ = results["tstream"]
    v_l, tolls_l, _ = results["lock"]
    np.testing.assert_allclose(np.asarray(v_t), np.asarray(v_l), rtol=1e-4)
    np.testing.assert_allclose(tolls_t, tolls_l, rtol=1e-4)
    print("[tp] all schemes agree with the sequential oracle ✓")


if __name__ == "__main__":
    main()
