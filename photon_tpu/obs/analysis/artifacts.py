"""Bench-artifact loading + backend attribution, shared by the bench
regression gate (``scripts/bench_compare.py``), the doc-figure sync
(``scripts/sync_bench_docs.py``), and the timeline analyzer's roofline
join.

Two artifact shapes exist in the repo:

* ``BENCH_DETAILS*.json`` — the flat details dict ``bench.py`` flushes
  after every stage;
* ``BENCH_r*.json`` — the round driver's wrapper: ``{"n", "cmd", "rc",
  "tail", "parsed"}`` where ``parsed`` is the bench's final stdout line
  (``{"metric", "value", ..., "extra_metrics": <details>}``) when the
  driver managed to parse it, and ``tail`` keeps the last ~2K characters
  of stdout otherwise. The salvage path resynthesizes a partial details
  dict from the tail fragment (same trick as sync_bench_docs), so even a
  truncated round still compares on the metrics that survived.

Backend attribution is the comparability core (ROADMAP "bench trajectory
caveat": r3/r5 ran on CPU fallback while r2 hit the accelerator — their
ratios must never be diffed as a trend). Per metric, the backend resolves
in order: the metric's own nested ``backend`` stamp → ``stage_backends``
(stamped per stage since PR 4) → the artifact's top-level ``backend`` →
``provenance.backend_summary`` → ``"unknown"``. ``"unknown"`` never
compares equal to anything, including itself: a delta you cannot place on
one backend is not a delta.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Optional

__all__ = [
    "ArtifactError",
    "BenchArtifact",
    "load_bench_artifact",
    "load_bench_details",
    "newest_artifacts",
    "metric_backend",
    "normalize_backend",
    "flatten_metrics",
]


class ArtifactError(ValueError):
    """The file is not a readable bench artifact (schema error)."""


def load_bench_details(path: str) -> dict:
    """Details dict from either artifact shape; raises ArtifactError."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except OSError as e:
        raise ArtifactError(f"{path}: {e}") from e
    except ValueError as e:
        raise ArtifactError(f"{path}: not valid JSON ({e})") from e
    if not isinstance(payload, dict):
        raise ArtifactError(f"{path}: top level is not a JSON object")
    if "tail" in payload and "cmd" in payload:  # BENCH_r* driver wrapper
        parsed = payload.get("parsed")
        if isinstance(parsed, dict):
            details = parsed.get("extra_metrics", parsed)
            if isinstance(details, dict):
                details = dict(details)
                # Surface the wrapper's headline as ordinary metrics so the
                # gate compares it like everything else.
                if isinstance(parsed.get("value"), (int, float)):
                    details.setdefault(
                        str(parsed.get("metric", "headline")),
                        parsed["value"])
                if isinstance(parsed.get("vs_baseline"), (int, float)):
                    details.setdefault("vs_baseline", parsed["vs_baseline"])
                return details
        return _salvage_tail(path, payload.get("tail") or "")
    return payload


def _salvage_tail(path: str, tail: str) -> dict:
    """Partial details from a truncated wrapper tail (last ~2K chars)."""
    for line in reversed(tail.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                out = json.loads(line)
            except json.JSONDecodeError:
                continue
            got = out.get("extra_metrics", out)
            if isinstance(got, dict):
                return got
    # The wrapper keeps only the LAST ~2K chars, which usually cuts the
    # result line's head. Resynthesize an object from the first complete
    # top-level key in the fragment (it ends with the result line's two
    # closing braces: extra_metrics' and the outer object's).
    frag = tail.strip()
    cut = frag.find(', "')
    if cut >= 0 and frag.endswith("}}"):
        try:
            return json.loads("{" + frag[cut + 2:-1])
        except json.JSONDecodeError:
            pass
    raise ArtifactError(f"{path}: no JSON result line in tail")


@dataclasses.dataclass
class BenchArtifact:
    """One loaded artifact with its comparability context."""

    path: str
    details: dict

    @property
    def name(self) -> str:
        return os.path.basename(self.path)

    @property
    def round(self) -> Optional[int]:
        import re

        m = re.search(r"BENCH_r(\d+)", self.name)
        return int(m.group(1)) if m else None

    @property
    def provenance(self) -> dict:
        p = self.details.get("provenance")
        return p if isinstance(p, dict) else {}

    @property
    def written_at(self) -> Optional[str]:
        return self.details.get("written_at") or self.provenance.get(
            "written_at")

    def backend_for(self, metric: str) -> str:
        return metric_backend(self.details, metric)

    def metrics(self) -> dict:
        return flatten_metrics(self.details)


def load_bench_artifact(path: str) -> BenchArtifact:
    return BenchArtifact(path=path, details=load_bench_details(path))


def newest_artifacts(root: str, k: int = 2) -> list[str]:
    """The ``k`` newest PARSEABLE checked-in artifacts, returned
    oldest→newest (ready for compare). Smoke artifacts never participate;
    unparseable wrappers are skipped.

    Recency is judged from ARTIFACT CONTENT, not file mtime: a fresh git
    clone stamps every checked-in artifact with the checkout time, which
    would make "newest" (and the compare's oldest→newest orientation)
    arbitrary in CI. The key is (``written_at``, round number, name) —
    ``written_at`` is the measurement's own provenance; artifacts
    predating the stamp fall back to their round number; the basename
    breaks remaining ties deterministically."""
    cands = []
    for pat in ("BENCH_r*.json", "BENCH_DETAILS*.json"):
        for p in glob.glob(os.path.join(root, pat)):
            if "smoke" in os.path.basename(p):
                continue
            try:
                art = load_bench_artifact(p)
            except ArtifactError:
                continue
            cands.append((
                art.written_at or "",
                art.round if art.round is not None else -1,
                art.name,
                p,
            ))
    cands.sort()
    return [p for _, _, _, p in cands[-k:]]


# ----------------------------------------------------------- backend maps

_REAL_BACKENDS = ("tpu", "gpu")

# metric-name prefix -> bench stage name (stage_backends key). Order
# matters: first match wins, longest prefixes first.
_STAGE_PREFIXES = (
    ("game_scale_", "game_scale"),
    ("game_scoring", "game"),
    ("game_", "game"),
    ("serve_", "serve"),
    ("ingest_", "ingest"),
    ("owlqn_", "owlqn_tron"),
    ("tron_", "owlqn_tron"),
    ("tuner_", "tuner"),
    ("sparse_race", "sparse_race"),
    ("fixed_effect", "fixed_effect_lbfgs"),
    ("roofline", "roofline"),
    ("numpy_multicore_baseline", "numpy_baseline"),
)


def normalize_backend(raw) -> str:
    """Collapse stamp variants to one comparable token.

    ``cpu-fallback`` and the baseline's ``host-cpu (...)`` prose are all
    CPU measurements; anything unrecognized stays verbatim (two artifacts
    on the same exotic backend still compare)."""
    if not raw or not isinstance(raw, str):
        return "unknown"
    low = raw.strip().lower()
    if low.startswith("cpu") or low.startswith("host-cpu"):
        return "cpu"
    for b in _REAL_BACKENDS:
        if low == b or low.startswith(b + "-") or low.startswith(b + " "):
            return b
    return low.split()[0] if low else "unknown"


def _stage_of(metric: str) -> Optional[str]:
    if metric.startswith("stage_seconds."):
        return metric.split(".", 1)[1]
    for prefix, stage in _STAGE_PREFIXES:
        if metric.startswith(prefix):
            return stage
    return None


def metric_backend(details: dict, metric: str) -> str:
    """The backend one flattened metric was measured on (see module doc
    for the resolution order)."""
    # 1. the metric's own nested stamp (fixed_effect_lbfgs.backend,
    #    roofline.backend, numpy_multicore_baseline.backend)
    head = metric.split(".", 1)[0]
    nested = details.get(head)
    if isinstance(nested, dict) and isinstance(nested.get("backend"), str):
        return normalize_backend(nested["backend"])
    # 2. per-stage stamp (PR 4's stage_backends)
    stage = _stage_of(metric)
    backends = details.get("stage_backends")
    if stage and isinstance(backends, dict) and backends.get(stage):
        return normalize_backend(backends[stage])
    # 3. artifact-level stamp
    if isinstance(details.get("backend"), str):
        return normalize_backend(details["backend"])
    # 4. provenance backend summary (this PR's stamp)
    prov = details.get("provenance")
    if isinstance(prov, dict):
        summ = prov.get("backend_summary")
        if isinstance(summ, dict) and isinstance(summ.get("backend"), str):
            return normalize_backend(summ["backend"])
        if isinstance(summ, str):
            return normalize_backend(summ)
    return "unknown"


# Keys that are bookkeeping/provenance, never metrics to diff.
_SKIP_KEYS = frozenset({
    "written_at", "git_head", "backend", "backend_fallback_reason",
    "stage_backends", "skipped_stages", "stage_errors", "provenance",
    "completed", "smoke_mode", "tpu_recovery_attempts", "tpu_recovery_tail",
    "last_real_hardware", "resumed_from_written_at", "resumed_from_backend",
    "sparse_race_skipped", "sparse_race_done", "baseline_model",
    # The numpy baseline is the DENOMINATOR (host speed), not a bench
    # result — its run-to-run drift is why PR 4 pinned it; never scored.
    "numpy_multicore_baseline",
    "n", "cmd", "rc", "tail", "parsed", "slo",
})


def flatten_metrics(details: dict, prefix: str = "") -> dict:
    """Numeric leaves as dotted names: the comparable surface of an
    artifact. Bools, strings, lists, and bookkeeping keys are skipped."""
    out: dict[str, float] = {}
    for key, val in details.items():
        if not prefix and key in _SKIP_KEYS:
            continue
        name = f"{prefix}{key}"
        if isinstance(val, bool):
            continue
        if isinstance(val, (int, float)):
            out[name] = float(val)
        elif isinstance(val, dict):
            out.update(flatten_metrics(val, prefix=f"{name}."))
    return out
