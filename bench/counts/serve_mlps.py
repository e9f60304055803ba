"""Work of one request's dense stages: the filtering MLP, its signature
projection and the ranking MLP over every candidate (float matmuls, at
the bf16 peak), and the embedding rows they read."""


def work(sizes: dict) -> tuple:
    """-> (operations, bytes) of one request, from the builder's sizes."""
    d = sizes["embed_dim"]
    filt = [(sizes["features"] + 1) * d, *sizes["filter_dims"]]
    rank = [4 * d, *sizes["rank_dims"]]
    mac = sum(a * b for a, b in zip(filt, filt[1:])) + d * sizes["bits"]
    mac += sizes["n_candidates"] * sum(a * b for a, b in zip(rank, rank[1:]))
    rows = sizes["features"] + sizes["history_len"] + sizes["n_candidates"] + 1
    return 2.0 * mac, rows * (d + 4)


def least_seconds(sizes: dict, peaks: dict) -> float:
    ops, nbytes = work(sizes)
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
