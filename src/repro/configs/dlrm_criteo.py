"""The paper's own workload: Facebook DLRM ranking on Criteo Kaggle.

This is the DLRM of iMARS's Table I: bottom MLP 256-128-32, top MLP
256-64-1, 26 one-hot ETs x 28000 rows at dim 32, pairwise-dot
interaction. It is not the DLRM the benchmark serves: that is DLRM-DCNv2,
the MLPerf recommendation model (Criteo 1TB multi-hot, dim 128, low-rank
cross network), whose configuration is
`bench/configs/dlrm-dcnv2-criteo1tb.json`.
"""
from repro.models.recsys import DLRMConfig


def model_config() -> DLRMConfig:
    return DLRMConfig()
