"""The comparison that decides ``correct``: each number a cell's limits
file names, against its limit. A run and the control (``control.py``) go
through this same function."""
from __future__ import annotations

from typing import Dict, Tuple


def judge(numbers: Dict[str, dict], readings: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """(every number read and within its limit, {name: {value, limit}}).
    A number with no reading fails; a reading with no limit is an error."""
    extra = set(readings) - set(numbers)
    if extra:
        raise ValueError(f"readings without a limit: {sorted(extra)}")
    checks, ok = {}, True
    for name, lim in numbers.items():
        value = readings.get(name)
        ok &= value is not None and value <= lim["limit"]
        checks[name] = {"value": value, "limit": lim["limit"]}
    return bool(ok), checks
