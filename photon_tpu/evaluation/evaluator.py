"""Evaluator objects, type parsing, and evaluation suites.

Parity: reference ⟦photon-api/.../evaluation/Evaluator.scala, EvaluatorType,
EvaluationSuite, EvaluationResults⟧ (SURVEY.md §2.2): evaluators know their
name and direction (is bigger better), suites bundle several with one primary
metric, and evaluator types parse from strings — "AUC", "RMSE",
"PRECISION@5:queryId", "AUC:queryId" for grouped variants.

The score input is the additive GAME score (raw linear scale); each evaluator
applies whatever link it needs, as in the reference (AUC ranks raw scores,
Poisson loss exponentiates, RMSE compares raw scores for linear regression).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Mapping, Optional, Sequence

import jax
import numpy as np

from photon_tpu.evaluation import metrics
from photon_tpu.obs import device_wait, trace_span

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Evaluator:
    """A named metric with an ordering. ``group_column`` marks grouped
    ("sharded") variants that need per-row group ids at evaluate time."""

    name: str
    kind: str                      # one of the _KINDS keys
    bigger_is_better: bool
    k: Optional[int] = None        # precision@k only
    group_column: Optional[str] = None

    def evaluate(
        self,
        scores: Array,
        labels: Array,
        weights: Array | None = None,
        group_ids: Array | None = None,
        num_groups: int | None = None,
    ) -> float:
        if self.kind == "AUC":
            v = metrics.auc(scores, labels, weights)
        elif self.kind == "RMSE":
            v = metrics.rmse(scores, labels, weights)
        elif self.kind == "SQUARED_LOSS":
            v = metrics.squared_loss(scores, labels, weights)
        elif self.kind == "LOGISTIC_LOSS":
            v = metrics.logistic_loss(scores, labels, weights)
        elif self.kind == "POISSON_LOSS":
            v = metrics.poisson_loss(scores, labels, weights)
        elif self.kind == "SMOOTHED_HINGE_LOSS":
            v = metrics.smoothed_hinge_loss(scores, labels, weights)
        elif self.kind == "GROUPED_AUC":
            if group_ids is None:
                raise ValueError(f"{self.name} needs group_ids")
            v = metrics.grouped_auc(scores, labels, group_ids, weights, num_groups)
        elif self.kind.startswith("GROUPED_"):
            if group_ids is None:
                raise ValueError(f"{self.name} needs group_ids")
            v = metrics.grouped_pointwise(
                self.kind[len("GROUPED_"):], scores, labels, group_ids,
                weights, num_groups,
            )
        elif self.kind == "PRECISION_AT_K":
            if group_ids is None:
                raise ValueError(f"{self.name} needs group_ids")
            v = metrics.grouped_precision_at_k(
                scores, labels, group_ids, self.k, weights, num_groups
            )
        else:  # pragma: no cover - parse() keeps kinds closed
            raise ValueError(f"unknown evaluator kind {self.kind}")
        with device_wait("evaluator"):
            return float(v)

    def better_than(self, a: float, b: float) -> bool:
        """Is metric value ``a`` strictly better than ``b`` (NaN never wins)?"""
        if np.isnan(a):
            return False
        if np.isnan(b):
            return True
        return a > b if self.bigger_is_better else a < b


_PRECISION_RE = re.compile(r"^PRECISION@(\d+):(.+)$", re.IGNORECASE)

_SIMPLE_KINDS = {
    "AUC": True,                 # kind -> bigger_is_better
    "RMSE": False,
    "SQUARED_LOSS": False,
    "LOGISTIC_LOSS": False,
    "POISSON_LOSS": False,
    "SMOOTHED_HINGE_LOSS": False,
}


def parse_evaluator(spec: str) -> Evaluator:
    """Parse a reference-style evaluator spec string.

    Forms: "AUC" | "RMSE" | "SQUARED_LOSS" | "LOGISTIC_LOSS" | "POISSON_LOSS"
    | "SMOOTHED_HINGE_LOSS" | "AUC:groupCol" | "PRECISION@k:groupCol".
    """
    s = spec.strip()
    m = _PRECISION_RE.match(s)
    if m:
        k, col = int(m.group(1)), m.group(2)
        return Evaluator(
            name=f"PRECISION@{k}:{col}", kind="PRECISION_AT_K",
            bigger_is_better=True, k=k, group_column=col,
        )
    if ":" in s:
        head, col = s.split(":", 1)
        head = head.strip().upper()
        if head in _SIMPLE_KINDS:
            # Grouped ("sharded"/Multi) family: AUC:col, RMSE:col,
            # LOGISTIC_LOSS:col, ... — reference ⟦MultiEvaluator⟧ by-group
            # averaging for every base metric.
            kind = "GROUPED_AUC" if head == "AUC" else f"GROUPED_{head}"
            return Evaluator(
                name=f"{head}:{col}", kind=kind,
                bigger_is_better=_SIMPLE_KINDS[head], group_column=col,
            )
        raise ValueError(f"unknown grouped evaluator {spec!r}")
    kind = s.upper()
    if kind not in _SIMPLE_KINDS:
        raise ValueError(f"unknown evaluator {spec!r}")
    return Evaluator(name=kind, kind=kind, bigger_is_better=_SIMPLE_KINDS[kind])


@dataclasses.dataclass(frozen=True)
class EvaluationResults:
    """Metric values keyed by evaluator name; first entry of ``suite`` is
    primary (reference ⟦EvaluationResults⟧)."""

    values: Mapping[str, float]
    primary_name: str

    @property
    def primary(self) -> float:
        return self.values[self.primary_name]

    def __repr__(self) -> str:
        vals = ", ".join(f"{k}={v:.6g}" for k, v in self.values.items())
        return f"EvaluationResults({vals}; primary={self.primary_name})"


@dataclasses.dataclass(frozen=True)
class EvaluationSuite:
    """Several evaluators over one validation set; the first is primary."""

    evaluators: Sequence[Evaluator]

    @staticmethod
    def parse(specs: Sequence[str]) -> "EvaluationSuite":
        if not specs:
            raise ValueError("at least one evaluator spec required")
        return EvaluationSuite(tuple(parse_evaluator(s) for s in specs))

    @property
    def primary(self) -> Evaluator:
        return self.evaluators[0]

    def evaluate(
        self,
        scores: Array,
        labels: Array,
        weights: Array | None = None,
        group_ids_by_column: Mapping[str, Array] | None = None,
        num_groups_by_column: Mapping[str, int] | None = None,
    ) -> EvaluationResults:
        values = {}
        for ev in self.evaluators:
            gid = None
            ng = None
            if ev.group_column is not None:
                if not group_ids_by_column or ev.group_column not in group_ids_by_column:
                    raise ValueError(
                        f"evaluator {ev.name} needs group ids for column "
                        f"{ev.group_column!r}"
                    )
                gid = group_ids_by_column[ev.group_column]
                if num_groups_by_column:
                    ng = num_groups_by_column.get(ev.group_column)
            with trace_span("validate.evaluate", cat="evaluation",
                            evaluator=ev.name).label(ev.name):
                values[ev.name] = ev.evaluate(scores, labels, weights, gid, ng)
        return EvaluationResults(values, self.evaluators[0].name)
