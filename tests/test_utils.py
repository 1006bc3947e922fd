"""SipHash-2-4 against the published reference vectors (whose test key
0x000102...0f equals Guava's default seed) and HTML color splitting
(ImageRegionRequestHandler.java:856-890 doc cases)."""

import pytest

from omero_ms_image_region_tpu.utils.color import split_html_color
from omero_ms_image_region_tpu.utils.siphash import (
    guava_siphash24_hex,
    siphash24,
)

# Official SipHash-2-4 test vectors (Aumasson & Bernstein reference code),
# key = 000102030405060708090a0b0c0d0e0f, input = first N bytes 00,01,...
SIPHASH_VECTORS = [
    0x726FDB47DD0E0E31,
    0x74F839C593DC67FD,
    0x0D6C8009D9A94F5A,
    0x85676696D7FB7E2D,
    0xCF2794E0277187B7,
    0x18765564CD99A68D,
    0xCBC9466E58FEE3CE,
    0xAB0200F58B01D137,
    0x93F5F5799A932462,
]


@pytest.mark.parametrize("n,expect", list(enumerate(SIPHASH_VECTORS)))
def test_siphash_reference_vectors(n, expect):
    data = bytes(range(n))
    assert siphash24(data) == expect


def test_guava_hex_formatting():
    # Guava prints the 64-bit hash's bytes little-endian first.
    h = siphash24(b"abc")
    assert guava_siphash24_hex("abc") == h.to_bytes(8, "little").hex()
    assert len(guava_siphash24_hex("")) == 16


@pytest.mark.parametrize(
    "color,expect",
    [
        ("abc", (0xAA, 0xBB, 0xCC, 0xFF)),
        ("abcd", (0xAA, 0xBB, 0xCC, 0xDD)),
        ("abbccd", (0xAB, 0xBC, 0xCD, 0xFF)),
        ("abbccdde", (0xAB, 0xBC, 0xCD, 0xDE)),
        ("FF0000", (255, 0, 0, 255)),
        ("not-a-color", None),
        ("12345", None),
        ("", None),
    ],
)
def test_split_html_color(color, expect):
    assert split_html_color(color) == expect
