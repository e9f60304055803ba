"""Paper Sec. IV-B: filtering-stage accuracy (HR) under the three configs —
(1) FP32 + cosine, (2) int8 + cosine, (3) int8 + LSH-Hamming (iMARS).

Synthetic MovieLens (real dataset unavailable offline): reproduces the
ORDERING + drop structure (int8 ~ fp32; LSH costs several points), not the
absolute 26.8/26.2/20.8 values. Paper deltas quoted in the output.
"""
import jax
import jax.numpy as jnp

from repro.data import synthetic
from repro.launch.runtime import enable_compile_cache
from repro.models import recsys as rs
from repro.optim import adamw
from repro.serving.recsys_engine import RecSysEngine, hit_rate


def train_and_eval(n_users=1500, n_items=800, steps=300, radius=112,
                   seed=0, scan_block=None, history_len=20):
    """Train a YoutubeDNN on the synthetic catalog and HR@10-eval the three
    accuracy configs. `scan_block` forces the filtering-stage NNS plan
    (None=auto, 0=dense, >0=streaming chunk) so accuracy can be re-anchored
    through the streaming path at any catalog size."""
    data = synthetic.make_movielens(n_users=n_users, n_items=n_items,
                                    history_len=history_len)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=data.histories.shape[1])
    params = rs.init_youtubednn(jax.random.key(seed), cfg)
    state = adamw.init_adamw_state(params)
    lg = jax.jit(jax.value_and_grad(
        lambda p, b: rs.filtering_loss(p, cfg, b)))
    for batch in synthetic.movielens_batches(data, 256, steps):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        _, g = lg(params, b)
        params, state = adamw.adamw_update(g, state, params, 3e-3,
                                           weight_decay=0.0)
    engine = RecSysEngine.build(params, cfg, radius=radius, n_candidates=64,
                                scan_block=scan_block)
    hrs = {mode: hit_rate(engine, data, k=10, mode=mode)
           for mode in ("fp32", "int8", "lsh")}
    return hrs


def rows(quick: bool = True):
    kw = dict(n_users=400, n_items=300, steps=250) if quick else {}
    hrs = train_and_eval(**kw)
    paper = {"fp32": 0.268, "int8": 0.262, "lsh": 0.208}
    out = []
    for mode in ("fp32", "int8", "lsh"):
        out.append((
            f"accuracy/hr10_{mode}", 0.0,
            f"hr={hrs[mode]:.3f};paper={paper[mode]:.3f}(real MovieLens)",
        ))
    out.append((
        "accuracy/ordering", 0.0,
        f"int8_drop={hrs['fp32']-hrs['int8']:+.3f}(paper +0.006);"
        f"lsh_drop={hrs['int8']-hrs['lsh']:+.3f}(paper +0.054);"
        f"ok={hrs['lsh'] <= hrs['int8'] + 0.02 and abs(hrs['fp32']-hrs['int8']) < 0.05}",
    ))
    return out


def main():
    enable_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.6f},{derived}")


if __name__ == "__main__":
    main()
