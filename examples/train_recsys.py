"""End-to-end driver (the paper's workload): train YoutubeDNN on synthetic
MovieLens-1M, then reproduce the Sec. IV-B accuracy study — HR@10 under
(1) fp32 + cosine, (2) int8 + cosine, (3) int8 + LSH-Hamming (iMARS).

  PYTHONPATH=src python examples/train_recsys.py [--users 2000] [--steps 400]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.data import synthetic
from repro.launch.runtime import enable_compile_cache
from repro.models import recsys as rs
from repro.serving.recsys_engine import RecSysEngine, hit_rate


def train(data, steps: int, seed: int = 0):
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=data.histories.shape[1])
    params = rs.init_youtubednn(jax.random.key(seed), cfg)
    fil = jax.jit(jax.value_and_grad(lambda p, b: rs.filtering_loss(p, cfg, b)))
    rnk = jax.jit(jax.value_and_grad(lambda p, b: rs.ranking_loss(p, cfg, b)))
    t0 = time.time()
    for i, batch in enumerate(synthetic.movielens_batches(data, 256, steps)):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        loss, g = fil(params, b)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg, params, g)
        if i % 100 == 0:
            print(f"  filtering step {i:4d} loss {float(loss):.4f}")
    for i, batch in enumerate(
            synthetic.movielens_rank_batches(data, 128, 16, steps // 2)):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        loss, g = rnk(params, b)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg, params, g)
        if i % 100 == 0:
            print(f"  ranking   step {i:4d} loss {float(loss):.4f}")
    print(f"  trained in {time.time() - t0:.1f}s")
    return params, cfg


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=2000)
    ap.add_argument("--items", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--radius", type=int, default=112)
    args = ap.parse_args()

    print("== generating synthetic MovieLens ==")
    data = synthetic.make_movielens(n_users=args.users, n_items=args.items)
    print("== training YoutubeDNN ==")
    params, cfg = train(data, args.steps)

    print("== accuracy study (paper Sec. IV-B) ==")
    engine = RecSysEngine.build(params, cfg, radius=args.radius,
                                n_candidates=64)
    rows = []
    for mode, label in (("fp32", "FP32 + cosine"),
                        ("int8", "int8 + cosine"),
                        ("lsh", "int8 + LSH-Hamming (iMARS)")):
        hr = hit_rate(engine, data, k=10, mode=mode)
        rows.append((label, hr))
        print(f"  HR@10 {label:28s}: {hr:.3f}")
    print("\npaper (real MovieLens-1M): 26.8% / 26.2% / 20.8% — synthetic "
          "data reproduces the ORDERING and the small-int8/larger-LSH drops")


if __name__ == "__main__":
    main()
