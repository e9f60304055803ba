"""Paper Sec. IV-C3: end-to-end latency/energy — 16.8x / 713x on MovieLens,
13.2x / 57.8x on Criteo — composed from the calibrated cost model, plus a
measured software-path throughput of the actual JAX pipeline on this host
(labeled as such; this container is CPU, not the paper's RTX 1080)."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model as cm
from repro.launch.runtime import enable_compile_cache


def rows(measure_software: bool = True):
    out = []
    ml = cm.end_to_end_movielens()
    out.append((
        "end_to_end/movielens/imars", ml["imars_latency_us"],
        f"qps={ml['imars_qps']:.0f}(paper 22025);"
        f"latency_x={ml['latency_speedup']:.2f}(paper 16.8);"
        f"energy_x={ml['energy_reduction']:.1f}(paper 713)",
    ))
    out.append((
        "end_to_end/movielens/gpu_paper", ml["gpu_latency_us"],
        f"qps={ml['gpu_qps']:.0f}(paper 1311)",
    ))
    cr = cm.end_to_end_criteo()
    out.append((
        "end_to_end/criteo/imars", cr["imars_latency_us"],
        f"latency_x={cr['latency_speedup']:.2f}(paper 13.2);"
        f"energy_x={cr['energy_reduction']:.1f}(paper 57.8)",
    ))

    if measure_software:
        from repro.data import synthetic
        from repro.models import recsys as rs
        from repro.serving import CacheStats, RecSysEngine, serve_step

        data = synthetic.make_movielens(n_users=500, n_items=300,
                                        history_len=8)
        cfg = rs.YoutubeDNNConfig(
            n_items=data.n_items,
            user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                           "occupation": 21, "zip_bucket": 250},
            history_len=8)
        params = rs.init_youtubednn(jax.random.key(0), cfg)
        freqs = np.bincount(data.histories[data.histories >= 0],
                            minlength=data.n_items)
        engine = RecSysEngine.build(params, cfg, radius=112,
                                    n_candidates=50, top_k=10,
                                    hot_rows=64, item_freqs=freqs)
        rng = np.random.default_rng(0)
        idx = rng.integers(0, data.n_users, 64)
        batch = {
            **{k: jnp.asarray(v[idx]) for k, v in data.user_feats.items()},
            "history": jnp.asarray(data.histories[idx]),
            "genre": jnp.asarray(data.genres[idx]),
        }
        stats = CacheStats.zero()
        r = serve_step(engine, batch, stats)  # compile
        stats = jax.block_until_ready(r)[3]
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            r = serve_step(engine, batch, stats)
            stats = r[3]
        jax.block_until_ready(r)
        dt = time.perf_counter() - t0
        per_query_us = dt / (n * 64) * 1e6
        out.append((
            "end_to_end/movielens/software_cpu", per_query_us,
            f"qps={1e6/per_query_us:.0f};hot_hit_rate={stats.hit_rate():.3f};"
            f"host=CPU(container, not GPU)",
        ))
    return out


def main():
    enable_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.6f},{derived}")


if __name__ == "__main__":
    main()
