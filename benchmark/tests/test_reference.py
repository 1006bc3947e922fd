"""The comparison that decides ``correct`` at a size a test can hold: the
plain reference's own libjpeg encoding passes, the control (the reference
one step below what the configuration states) does not, and an answer
altered where it is produced does not."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import datagen                              # noqa: E402
from benchmark.references import render_jpeg as reference  # noqa: E402
from benchmark.control import CONTROLS                     # noqa: E402

COLORS = [(255, 0, 0), (0, 255, 0), (0, 0, 255), (255, 255, 0)]
WINDOWS = [(1432, 42782), (1571, 33474), (1548, 28133), (1441, 21344)]
LIMITS = json.load(open(os.path.join(
    os.path.dirname(HERE), "configs", "wsi4-u16-t1024.json")))["limits"]


@pytest.fixture(scope="module")
def tile():
    return datagen.synthetic_tiles(np.random.default_rng(2500000001),
                                   1, 4, 512, 512)[0]


def passes(numbers: dict) -> bool:
    return "error" not in numbers and all(
        numbers[k] <= LIMITS[k] for k in LIMITS)


def test_ijg_tables_are_libjpegs(tile):
    for quality in (50, 75, 80, 90, 95):
        body = reference.libjpeg_bytes(
            reference.render_rgb(tile, WINDOWS, COLORS), quality)
        _, tables = reference.decode(body)
        qy, qc = reference.ijg_tables(quality)
        assert np.array_equal(tables[0], qy)
        assert np.array_equal(tables[1], qc)


def test_render_semantics_on_known_pixels():
    raw = np.array([[[0, 1000, 2000, 65535]],
                    [[500, 500, 500, 500]]], np.uint16)
    rgb = reference.render_rgb(raw, [(1000, 2000), (0, 1000)],
                               [(255, 0, 0), (0, 0, 255)])
    assert rgb[0, :, 0].tolist() == [0, 0, 255, 255]
    assert rgb[0, :, 2].tolist() == [128, 128, 128, 128]
    assert rgb[0, :, 1].tolist() == [0, 0, 0, 0]


def test_the_reference_in_the_programs_place_passes(tile):
    body = reference.control_body(tile, WINDOWS, COLORS, 90, None)
    numbers = reference.compare(body, tile, WINDOWS, COLORS, 90)
    assert numbers["qtable_diff"] == 0
    assert numbers["excess_err"] == pytest.approx(0.0, abs=1e-9)
    assert passes(numbers)


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_each_control_comes_out_not_correct(tile, name):
    body = reference.control_body(tile, WINDOWS, COLORS, **CONTROLS[name])
    numbers = reference.compare(body, tile, WINDOWS, COLORS, 90)
    assert not passes(numbers), numbers
    if CONTROLS[name]["quality"] != 90:
        assert numbers["qtable_diff"] > 0
    if CONTROLS[name]["data_bits"] is not None:
        assert numbers["excess_err"] > LIMITS["excess_err"]


@pytest.mark.parametrize("fault", ["window", "channel", "tile", "garbage"])
def test_an_altered_answer_comes_out_not_correct(tile, fault):
    windows, raw, colors = list(WINDOWS), tile, COLORS
    if fault == "window":
        windows[0] = (windows[0][0], windows[0][1] * 3 // 4)
    elif fault == "channel":
        colors = COLORS[:3] + [(0, 0, 0)]          # one channel left out
    elif fault == "tile":
        raw = tile[:, ::-1]                        # another region's data
    body = reference.control_body(raw, windows, colors, 90, None)
    if fault == "garbage":
        body = body[:200]
    assert not passes(reference.compare(body, tile, WINDOWS, COLORS, 90))
