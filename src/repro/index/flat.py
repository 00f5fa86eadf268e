"""FLAT (exact brute-force) and SQ-compressed flat indexes.

FLAT is both a real index (small segments, growing-slice temporary scans)
and the recall oracle every other index is measured against.
"""

from __future__ import annotations

import numpy as np

from ..core.collection import Metric
from ..kernels import ops
from .base import VectorIndex, normalize_if_cosine, scan_metric


class FlatIndex(VectorIndex):
    KIND = "flat"

    def __init__(self, metric: Metric = Metric.L2, **params):
        super().__init__(metric, **params)
        self.vectors: np.ndarray | None = None
        # The scan's operand: ``vectors`` kept on the device from the first
        # search on, for as long as this index lives (``ops.resident``).
        self._operand = None

    def build(self, vectors: np.ndarray) -> None:
        self.vectors = normalize_if_cosine(self.metric, np.asarray(vectors, np.float32))
        self.num_rows = len(self.vectors)
        self._operand = None

    def search(self, queries, k, valid=None):
        q = normalize_if_cosine(self.metric, np.asarray(queries, np.float32))
        if self._operand is None:
            self._operand = ops.resident(self.vectors)
        return ops.topk_scan(q, self._operand, k, metric=scan_metric(self.metric), valid=valid)

    def _state(self):
        return {"vectors": self.vectors}

    def _load_state(self, state):
        self.vectors = state["vectors"]
        self.num_rows = len(self.vectors)
        self._operand = None


class SQIndex(VectorIndex):
    """Scalar-quantized flat index: 4x memory saving, distances on codes."""

    KIND = "sq"

    def __init__(self, metric: Metric = Metric.L2, **params):
        super().__init__(metric, **params)
        self.codes: np.ndarray | None = None
        self.vmin: np.ndarray | None = None
        self.vmax: np.ndarray | None = None

    def build(self, vectors: np.ndarray) -> None:
        x = normalize_if_cosine(self.metric, np.asarray(vectors, np.float32))
        self.vmin = x.min(axis=0) if len(x) else np.zeros(x.shape[1], np.float32)
        self.vmax = x.max(axis=0) if len(x) else np.ones(x.shape[1], np.float32)
        self.codes = ops.sq_encode(x, self.vmin, self.vmax)
        self.num_rows = len(x)

    def search(self, queries, k, valid=None):
        q = normalize_if_cosine(self.metric, np.asarray(queries, np.float32))
        return ops.sq_topk_scan(
            q, self.codes, self.vmin, self.vmax, k,
            metric=scan_metric(self.metric), valid=valid,
        )

    def _state(self):
        return {"codes": self.codes, "vmin": self.vmin, "vmax": self.vmax}

    def _load_state(self, state):
        self.codes = state["codes"]
        self.vmin = state["vmin"]
        self.vmax = state["vmax"]
        self.num_rows = len(self.codes)
