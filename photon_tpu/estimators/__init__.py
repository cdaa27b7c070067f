"""Estimator/Transformer API layer — reference ⟦photon-api/.../estimators,
.../transformers⟧ (SURVEY.md §1 L6)."""
from photon_tpu.estimators.config import (
    CoordinateDataConfig,
    FactoredRandomEffectDataConfig,
    FixedEffectDataConfig,
    GameOptimizationConfiguration,
    GLMOptimizationConfiguration,
    RandomEffectDataConfig,
    reg_weight_sweep,
)
from photon_tpu.estimators.game_estimator import (
    GameEstimator,
    GameFitResult,
    build_re_dataset_from_bundle,
    fit_breakdown,
    select_best,
)
from photon_tpu.estimators.game_transformer import GameTransformer

__all__ = [
    "CoordinateDataConfig",
    "FactoredRandomEffectDataConfig",
    "FixedEffectDataConfig",
    "RandomEffectDataConfig",
    "GLMOptimizationConfiguration",
    "GameOptimizationConfiguration",
    "reg_weight_sweep",
    "GameEstimator",
    "GameFitResult",
    "GameTransformer",
    "build_re_dataset_from_bundle",
    "fit_breakdown",
    "select_best",
]
