"""Prometheus text exposition -> numbers: copied from ``chip_smoke.py``."""

from __future__ import annotations


def parse_metrics(text: str) -> dict:
    """``{'name{labels}': value}`` of one ``/metrics`` body."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            pass
    return out


def series(m: dict, family: str, **labels) -> float:
    """Sum of every series of ``family`` carrying ``labels``."""
    total = 0.0
    for key, value in m.items():
        name, _, rest = key.partition("{")
        if name == family and all(f'{k}="{v}"' in rest
                                  for k, v in labels.items()):
            total += value
    return total


def delta(m0: dict, m1: dict, family: str, **labels) -> float:
    """Growth of a counter family between two scrapes."""
    return series(m1, family, **labels) - series(m0, family, **labels)
