from janusgraph_tpu.olap.features.dense_program import (  # noqa: F401
    DenseVertexProgram,
    MessageMode,
)
from janusgraph_tpu.olap.features.kernels import (  # noqa: F401
    FEATURE_TIERS,
    dense_transform,
    ell_row_dsts,
    hybrid_row_dsts,
    pad_features,
    pick_feature_tier,
    sddmm_ell_aggregate,
    sddmm_hybrid_aggregate,
    tree_dot,
    tree_matmul,
)
