"""End-to-end driver: train a ~100M-param dense LM for a few hundred steps
through the full stack (TStream data pipeline, AdamW+WSD, checkpointing,
crash-resume).

    PYTHONPATH=src python examples/train_100m.py [--steps 300]
"""
import argparse
import dataclasses
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import setup_compile_cache
from repro.configs import get_arch
from repro.data import PipelineConfig, StreamingPipeline
from repro.models import init_params, loss_fn
from repro.optim import AdamWConfig, adamw_init, adamw_update, wsd_schedule
from repro.runtime import TrainLoop, TrainLoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="results/ckpt_100m")
    args = ap.parse_args()
    setup_compile_cache()

    # ~100M params: 8 layers, d=768, ffn 3072, vocab 32k
    base = get_arch("minicpm-2b")
    cfg = dataclasses.replace(
        base, name="dense-100m", n_layers=8, d_model=768, n_heads=12,
        n_kv_heads=12, head_dim=64, d_ff=3072, vocab=32_000,
        residual_scale=1.0)
    n = cfg.param_count()
    print(f"[100m] {cfg.name}: {n/1e6:.1f}M params")

    pipe = StreamingPipeline(PipelineConfig(vocab=cfg.vocab, seq_len=256,
                                            batch=8))
    # keep the stream-side statistics engine hot during training
    ingest_rng = np.random.default_rng(1)
    pipe.ingest(ingest_rng, 256)
    print(f"[100m] mixture weights from TStream stats engine: "
          f"{np.round(pipe.mixture_weights()[:4], 4)} ...")

    opt_cfg = AdamWConfig(lr=3e-4, state_dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    opt_state = adamw_init(params, opt_cfg)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, remat="none"))(params)
        lr = wsd_schedule(opt_state["step"], warmup=20,
                          stable=args.steps - 80, decay=60)
        p2, s2 = adamw_update(params, grads, opt_state, opt_cfg, lr)
        return p2, s2, loss

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    loop = TrainLoop(
        TrainLoopConfig(ckpt_dir=args.ckpt_dir, ckpt_every=100,
                        max_steps=args.steps),
        jax.jit(train_step, donate_argnums=(0, 1)),
        lambda step, rng: pipe.batch_for_step(step),
        params, opt_state)

    t0 = time.time()
    loop.run()
    dt = time.time() - t0
    first = np.mean(loop.losses[:10])
    last = np.mean(loop.losses[-10:])
    tok_s = args.steps * 8 * 256 / dt
    print(f"[100m] {args.steps} steps in {dt/60:.1f} min "
          f"({tok_s:.0f} tok/s host)")
    print(f"[100m] loss {first:.3f} -> {last:.3f}")
    assert last < first - 0.5, "loss must fall substantially"
    print("[100m] training learns ✓ (checkpoints in " + args.ckpt_dir + ")")


if __name__ == "__main__":
    main()
