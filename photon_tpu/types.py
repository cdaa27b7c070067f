"""Core type aliases and task enumeration.

Parity: reference ⟦photon-api/.../Types.scala⟧ and ⟦TaskType.scala⟧ (paths
unverified — reference mount empty; see SURVEY.md provenance warning).
"""
from __future__ import annotations

import enum

# jax.default_backend() names that mean "a real accelerator serves this
# process". ONE source of truth for every consumer — the backend guard's
# strict policy, artifact stamping, and the drivers' accelerator-layout
# auto-attach — so the allowlist cannot silently diverge between writer and
# reader.
REAL_ACCELERATOR_BACKENDS = ("tpu",)

# Type aliases mirroring the reference's Types.scala
CoordinateId = str
REId = str          # random-effect entity id (e.g. a userId value)
REType = str        # random-effect type (e.g. "userId" — the column name)
FeatureShardId = str
UniqueSampleId = int


class TaskType(enum.Enum):
    """Training objective family.

    Parity: reference ⟦photon-api/.../TaskType.scala⟧ — LOGISTIC_REGRESSION,
    LINEAR_REGRESSION, POISSON_REGRESSION, SMOOTHED_HINGE_LOSS_LINEAR_SVM.
    """

    LOGISTIC_REGRESSION = "LOGISTIC_REGRESSION"
    LINEAR_REGRESSION = "LINEAR_REGRESSION"
    POISSON_REGRESSION = "POISSON_REGRESSION"
    SMOOTHED_HINGE_LOSS_LINEAR_SVM = "SMOOTHED_HINGE_LOSS_LINEAR_SVM"

    @classmethod
    def parse(cls, s: str) -> "TaskType":
        key = s.strip().upper()
        aliases = {
            "LOGISTIC": cls.LOGISTIC_REGRESSION,
            "LINEAR": cls.LINEAR_REGRESSION,
            "POISSON": cls.POISSON_REGRESSION,
            "SVM": cls.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            "SMOOTHED_HINGE": cls.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
        }
        if key in aliases:
            return aliases[key]
        return cls(key)
