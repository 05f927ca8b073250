"""Lightweight metric logging (in-memory history)."""

from __future__ import annotations

from typing import Dict, List, Optional


class MetricHistory:
    """In-memory step -> metrics store with simple reductions."""

    def __init__(self) -> None:
        self._rows: List[Dict[str, float]] = []

    def log(self, step: int, **metrics: float) -> None:
        row = {"step": float(step)}
        row.update({k: float(v) for k, v in metrics.items()})
        self._rows.append(row)

    @property
    def rows(self) -> List[Dict[str, float]]:
        return list(self._rows)

    def series(self, key: str) -> List[float]:
        return [r[key] for r in self._rows if key in r]

    def last(self, key: str) -> Optional[float]:
        s = self.series(key)
        return s[-1] if s else None
