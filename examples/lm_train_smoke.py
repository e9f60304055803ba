"""Train a ~100M-param LM for a few hundred steps with the full production
stack: fault-tolerant TrainLoop, atomic checkpointing (+auto-resume),
background-prefetched data pipeline, gradient accumulation, remat, chunked
cross-entropy, AdamW.

  PYTHONPATH=src python examples/lm_train_smoke.py --steps 200
  (re-run the same command to watch it resume from the checkpoint)
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.data.lm_data import PrefetchIterator, synthetic_token_stream
from repro.distributed import training as tr
from repro.distributed.fault_tolerance import FaultPolicy, TrainLoop
from repro.launch.runtime import enable_compile_cache


def small_lm() -> ModelConfig:
    # ~100M params: 12L x 512d x 8H, vocab 8192
    return ModelConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=8192,
        dtype="float32")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", type=str, default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    cfg = small_lm()
    pcfg = ParallelConfig(remat="block", logit_chunk=64,
                          grad_accum={"smoke": 2})
    shape = ShapeConfig("smoke", "train", args.seq, args.batch)

    from repro.configs.base import param_count_dense
    print(f"model: {cfg.name} ~{param_count_dense(cfg)/1e6:.0f}M params")

    step_fn = jax.jit(tr.make_train_step(cfg, pcfg, shape, base_lr=3e-4,
                                         warmup=20, total_steps=args.steps),
                      donate_argnums=0)

    def batches():
        stream = synthetic_token_stream(cfg.vocab_size, args.seq,
                                        args.batch, seed=0)
        accum = pcfg.accum_for("smoke")
        mb = args.batch // accum
        for item in stream:
            yield {
                "tokens": jnp.asarray(
                    item["tokens"].reshape(accum, mb, args.seq)),
                "labels": jnp.asarray(
                    item["labels"].reshape(accum, mb, args.seq)),
            }

    data = PrefetchIterator(batches(), depth=4)
    ckpt = Checkpointer(args.ckpt, keep=2, async_=True)
    loop = TrainLoop(step_fn, ckpt, FaultPolicy(checkpoint_every=50))

    state, start = loop.resume_or_init(
        lambda: tr.init_train_state(cfg, pcfg, jax.random.key(0)))
    print(f"starting at step {start}")

    class LoggingData:
        def __iter__(self):
            return self

        def __next__(self):
            return next(data)

    final, end = loop.run(state, LoggingData(), args.steps,
                          start_step=start)
    losses = [r.metrics["loss"] for r in loop.records]
    if losses:
        print(f"steps {start}->{end}; loss {losses[0]:.3f} -> "
              f"{losses[-1]:.3f} (structured stream: should fall)")
    if loop.straggler_events:
        print("straggler steps:", loop.straggler_events)


if __name__ == "__main__":
    main()
