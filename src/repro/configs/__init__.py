"""Assigned architecture configs. Importing this package registers all
eleven ``--arch`` ids (plus the paper's SVM dataset configs in ``svm_datasets``)."""

from repro.configs import (  # noqa: F401
    granite4_h_micro,
    internlm2_1p8b,
    llama32_3b,
    mamba2_2p7b,
    paligemma_3b,
    phi35_moe,
    qwen25_3b,
    qwen3_moe,
    smollm_360m,
    whisper_base,
    zamba2_1p2b,
)
from repro.configs import svm_datasets  # noqa: F401
from repro.configs.sync_presets import (  # noqa: F401
    SYNC_PRESETS,
    get_sync_preset,
)
