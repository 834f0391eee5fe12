"""The run's last line, and the numbers compared beside their limits."""
from __future__ import annotations

import json
import math
import sys
from typing import Dict, List, Optional, Tuple

KEYS = ("correct", "attempted", "failed", "metrics", "device")


def device_info(chips: int) -> dict:
    """The devices used as JAX reports them; the peak is the fullest
    chip's."""
    import jax
    devs = jax.devices()[:chips]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def line(correct: bool, attempted: int, failed: int,
         metrics: Dict[str, dict], device: dict,
         checks: List[Tuple[str, float, float]],
         breakdown: Optional[dict] = None) -> dict:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


def validate(obj: dict) -> None:
    """Raise if ``obj`` is not a well-formed result line."""
    for k in KEYS:
        if k not in obj:
            raise ValueError(f"result line lacks {k!r}")
    if not isinstance(obj["correct"], bool):
        raise ValueError("correct must be a bool")
    for k in ("attempted", "failed"):
        if not isinstance(obj[k], int) or obj[k] < 0:
            raise ValueError(f"{k} must be a whole number")
    for name, m in obj["metrics"].items():
        if set(m) != {"value", "unit"} or not math.isfinite(m["value"]):
            raise ValueError(f"metric {name}: {m}")
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in obj["device"]:
            raise ValueError(f"device lacks {k!r}")
    if "breakdown" in obj:
        for k in ("device_ops", "idle_gaps"):
            if len(obj["breakdown"].get(k, [])) > 10:
                raise ValueError(f"breakdown.{k} has more than 10 entries")
    if list(obj)[-1] != "checks":
        raise ValueError("the compared numbers must come last")


def emit(obj: dict, checks: List[Tuple[str, float, float]]) -> None:
    """The compared numbers as the last lines of standard error, the
    result as the last line of standard output."""
    validate(obj)
    for name, v, lim in checks:
        print(f"check {name} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(obj), flush=True)
