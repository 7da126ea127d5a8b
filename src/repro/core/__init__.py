"""Core: the paper's coded-matmul schemes, decoding, bounds, and simulator."""
from repro.core.api import (
    CodedMatmulPlan,
    coded_matmul,
    extend_plan,
    fused_worker_products,
    make_plan,
    shrink_plan,
    uncoded_matmul,
    worker_products,
)
from repro.core.bounds import BoundsReport, choose_s, conservative_L, plan_p_prime
from repro.core.decoding import (
    DecodePanel,
    DecodePanelCache,
    decode,
    decode_masked,
    decode_with_panel,
    digit_extract,
    make_decode_panel,
)
from repro.core.partition import GridSpec, block_decompose, block_recompose
from repro.core.points import extend_points, make_points
from repro.core.schemes import (
    EntangledBoundedScheme,
    PolynomialCodeYu,
    Scheme,
    TradeoffScheme,
    make_scheme,
)
from repro.core.simulator import (
    LatencyModel,
    WorkerTimes,
    completion_quantile,
    masked_completion_cdf,
    masked_completion_mean,
    masked_completion_quantile,
    simulate_completion,
)

__all__ = [
    "CodedMatmulPlan", "coded_matmul", "make_plan",
    "uncoded_matmul", "worker_products", "fused_worker_products",
    "extend_plan", "shrink_plan",
    "BoundsReport", "choose_s", "conservative_L", "plan_p_prime",
    "decode", "decode_masked", "digit_extract",
    "DecodePanel", "DecodePanelCache", "decode_with_panel",
    "make_decode_panel",
    "GridSpec", "block_decompose", "block_recompose",
    "extend_points", "make_points",
    "EntangledBoundedScheme", "PolynomialCodeYu", "Scheme", "TradeoffScheme",
    "make_scheme",
    "LatencyModel", "WorkerTimes", "simulate_completion",
    "completion_quantile", "masked_completion_cdf",
    "masked_completion_mean", "masked_completion_quantile",
]
