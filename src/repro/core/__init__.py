"""SLaB core: decomposition algorithm, baselines, packing, forward ops."""
from repro.core.slab import (  # noqa: F401
    SLaBConfig,
    SLaBDecomposition,
    compression_ratio,
    compressed_bits,
    decomposition_error,
    keep_fraction,
    reconstruct,
    slab_decompose,
)
from repro.core.apply import slab_linear, to_dense  # noqa: F401
from repro.core.compressor import (  # noqa: F401
    CompressedLinear,
    Compressor,
    LinearStats,
)
from repro.core.plan import (  # noqa: F401
    CalibrationSpec,
    CompressionPlan,
    PlanRule,
    plan_for_method,
)
