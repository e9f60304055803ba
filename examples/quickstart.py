"""Quickstart: the iMARS primitives in 60 seconds.

  PYTHONPATH=src python examples/quickstart.py

Builds a small quantized embedding store, runs fused lookups/pooling, LSH +
fixed-radius Hamming NNS, threshold top-k, and prints what the iMARS fabric
would spend per query (the paper's Tables I-III composed live).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model as cm, mapping
from repro.core.embedding import embedding_bag, init_table, table_to_dense
from repro.core.lsh import lsh_signature, make_lsh_projections
from repro.core.nns import fixed_radius_nns
from repro.core.topk import threshold_topk
from repro.launch.runtime import enable_compile_cache


def main():
    enable_compile_cache()
    key = jax.random.key(0)
    print("== iMARS quickstart ==")

    # 1. int8 embedding table (one CMA bank) + fused pooled lookups
    table = init_table(key, n_rows=3000, dim=32)
    ids = jnp.array([[3, 17, 256, -1], [7, -1, -1, -1]])
    pooled = embedding_bag(table, ids, mode="sum")
    print(f"pooled lookups: ids {ids.shape} -> {pooled.shape}, "
          f"table stored int8 ({table.values.dtype})")

    # 2. LSH signatures + TCAM-style fixed-radius NNS
    proj = make_lsh_projections(jax.random.key(1), 32, 256)
    item_sigs = lsh_signature(table_to_dense(table), proj)
    user_vec = table_to_dense(table)[42:43] * 1.05 + 0.005 * jax.random.normal(
        jax.random.key(3), (1, 32))
    query_sig = lsh_signature(user_vec, proj)
    res = fixed_radius_nns(query_sig, item_sigs, radius=64, max_candidates=8)
    print(f"NNS: query matches {int(res.counts[0])} items within r=64; "
          f"nearest: id={int(res.indices[0, 0])} d={int(res.distances[0, 0])}")

    # 3. CTR-buffer threshold top-k
    ctr = jax.nn.sigmoid(jax.random.normal(jax.random.key(2), (1, 8)))
    top = threshold_topk(ctr, threshold=0.5, k=3)
    print(f"threshold top-k: {int(top.counts[0])} above 0.5 -> "
          f"{np.asarray(top.indices[0]).tolist()}")

    # 4. what the FeFET fabric would spend (paper Tables I-III)
    ml = mapping.movielens_mapping()
    print(f"\nTable I mapping (MovieLens): {ml.banks} banks / {ml.mats} mats"
          f" / {ml.cmas} CMAs  (paper: 7/8/54)")
    t3 = cm.table3_model()
    for stage, row in t3.items():
        print(f"Table III {stage:12s}: {row['model_latency_us']:.3f} us "
              f"{row['model_energy_uj']:.3f} uJ  "
              f"(paper: {row['paper_latency_us']:.2f} us "
              f"{row['paper_energy_uj']:.2f} uJ)")
    e2e = cm.end_to_end_movielens()
    print(f"end-to-end: {e2e['imars_qps']:.0f} qps, "
          f"{e2e['latency_speedup']:.1f}x latency / "
          f"{e2e['energy_reduction']:.0f}x energy vs GPU "
          f"(paper: 22025 qps, 16.8x / 713x)")


if __name__ == "__main__":
    main()
