"""Host utilities: hashing, colors, config, telemetry (request traces,
bucketed histograms, readiness state)."""

from .siphash import siphash24, guava_siphash24_hex
from .color import split_html_color

__all__ = ["siphash24", "guava_siphash24_hex", "split_html_color"]
