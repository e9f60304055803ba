"""Work of the DLRM-DCNv2 serve path, from the configuration alone.

A sample (one candidate of a request) reads one int8 row and its f32
scale a bag slot, plus the slot's int32 id, and runs the bottom MLP, the
low-rank cross network and the top MLP: float multiply-adds counted at
the bf16 peak, so that no precision can read above its least time.
"""


def rank_flops(cfg: dict) -> float:
    """Operations of one sample's dense stack (2 a multiply-add)."""
    width = (len(cfg["bag_sizes"]) + 1) * cfg["embed_dim"]
    bottom = [cfg["dense_features"], *cfg["bottom_dims"]]
    top = [width, *cfg["top_dims"]]
    mac = sum(a * b for a, b in zip(bottom, bottom[1:]))
    mac += cfg["cross_layers"] * 2 * width * cfg["cross_rank"]
    mac += sum(a * b for a, b in zip(top, top[1:]))
    return 2.0 * mac


def lookup_rows(cfg: dict) -> int:
    """Table rows one sample reads: its bag slots."""
    return sum(cfg["bag_sizes"])


def lookup_bytes(cfg: dict) -> float:
    """Bytes one sample's lookup reads: each row's int8 values and f32
    scale, and its int32 id."""
    return lookup_rows(cfg) * (cfg["embed_dim"] + 4 + 4)


def lookup_seconds(cfg: dict, samples: float, peaks: dict) -> float:
    """The least time of the lookup of `samples` samples, at HBM peak."""
    return samples * lookup_bytes(cfg) / peaks["hbm_bytes_per_s"]


def rank_seconds(cfg: dict, samples: float, peaks: dict) -> float:
    """The least time of the dense stack of `samples` samples, at the
    bf16 peak."""
    return samples * rank_flops(cfg) / peaks["bf16_flops"]


def request_seconds(cfg: dict, peaks: dict) -> float:
    """The least seconds one request needs on the chip: the larger of
    its candidates' operations at the bf16 peak and their bytes at HBM
    peak."""
    return max(rank_seconds(cfg, cfg["candidates"], peaks),
               lookup_seconds(cfg, cfg["candidates"], peaks))


def batch_samples(ctx):
    """Samples a dispatched batch carried in the window (padding
    requests included: the device computes them), or None."""
    from bench import stats

    batches = stats.counter_delta(ctx.snap0, ctx.snap1, "serving.batches")
    if not batches:
        return None
    requests = (stats.counter_delta(ctx.snap0, ctx.snap1, "serving.served")
                + stats.counter_delta(ctx.snap0, ctx.snap1,
                                      "serving.padded"))
    return requests / batches * ctx.work["config"]["candidates"]
