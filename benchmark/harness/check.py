"""The comparison that decides ``correct``: each number beside its limit."""

from __future__ import annotations

import math


class Comparison:
    def __init__(self, name: str, value: float, limit: float, why: str):
        self.name, self.value, self.limit, self.why = (
            name, float(value), float(limit), why,
        )

    @property
    def ok(self) -> bool:
        # NaN never passes
        return self.value <= self.limit

    def entry(self) -> dict:
        """The number beside its limit, as the result line carries it (a
        value that is no finite number goes as its name: JSON has no NaN)."""
        value = self.value if math.isfinite(self.value) else repr(self.value)
        return {"value": value, "limit": self.limit}

    def line(self) -> str:
        return (
            f"[check] {self.name}: value={self.value!r} limit={self.limit!r} "
            f"{'ok' if self.ok else 'FAILED'} ({self.why})"
        )


def disagreement(got, want) -> float:
    """Share of rows on which two answer vectors differ."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise ValueError(f"answers {got.shape} against reference {want.shape}")
    if got.size == 0:
        raise ValueError("nothing to compare")
    return float((got != want).mean())


def decide(comparisons) -> bool:
    for c in comparisons:
        print(c.line(), flush=True)
    return bool(comparisons) and all(c.ok for c in comparisons)
