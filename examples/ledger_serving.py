"""Streaming Ledger under load: concurrent transfers with aborts.

Demonstrates §IV-C2 abort handling (rejected transfers leave no partial
effects) and conservation of money under the dual-mode engine.

    PYTHONPATH=src python examples/ledger_serving.py
"""
import numpy as np

from repro.apps import SL
from repro.compile_cache import setup_compile_cache
from repro.core import DualModeEngine, EngineConfig


def main():
    setup_compile_cache()
    rng = np.random.default_rng(7)
    stream = SL.gen_events(rng, 3000)
    store = SL.make_store()
    before = float(np.asarray(store.values).sum())

    eng = DualModeEngine(SL, store, EngineConfig(scheme="tstream",
                                                 abort_repass=True))
    outs, values = eng.run_stream(store.values, stream, punct_interval=500)

    rejected = np.concatenate([np.asarray(o["rejected"]) for o in outs])
    after = float(np.asarray(values).sum())
    deposits = stream["amount"][~stream["is_transfer"]][: len(rejected)]
    n_proc = (len(rejected) // 500) * 500
    dep_amt = stream["amount"][:n_proc][~stream["is_transfer"][:n_proc]]
    print(f"[sl] processed {n_proc} events, "
          f"{int(rejected.sum())} transfers rejected (insufficient funds)")
    print(f"[sl] ledger total {before:.1f} -> {after:.1f} "
          f"(deposited {2 * dep_amt.sum():.1f})")
    np.testing.assert_allclose(after - before, 2 * dep_amt.sum(), rtol=1e-3)
    print("[sl] conservation holds: committed transfers moved, "
          "rejected ones left no partial effects ✓")


if __name__ == "__main__":
    main()
