"""dlrm_mfu: the share of one chip's peak that DLRM's served rate uses:
qps times the least seconds a request needs (`counts/dlrm.py`: its
candidates' operations at the bf16 peak, or their bytes at HBM peak,
whichever is larger), in percent."""


def read(ctx):
    qps = ctx.values.get("qps")
    if qps is None:
        return None
    return 100.0 * qps * ctx.count("dlrm").request_seconds(
        ctx.work["config"], ctx.peaks)
