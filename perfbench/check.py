"""A number compared by the check that decides ``correct``."""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Compared:
    """``value`` passes when it is a finite number at most ``limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit
