"""The four-chip cell's traffic mix is the one-chip ``knobs`` mix under its
own name: every key but ``name`` and ``about`` is the same, so both cells
run the same grids from the same seed."""

import json
import os

from bench.grid import GridSource
from bench.tests.helpers import REPO


def _load(kind, name):
    with open(os.path.join(REPO, "bench", kind, name + ".json")) as f:
        return json.load(f)


def test_knobs_x4_is_knobs():
    one, four = _load("traffic", "knobs"), _load("traffic", "knobs-x4")
    strip = lambda t: {k: v for k, v in t.items() if k not in ("name", "about")}
    assert strip(one) == strip(four)
    config = _load("configs", "quad48-bots")
    seed = 3_141_592_653
    assert GridSource(one, config, seed).next() == \
        GridSource(four, config, seed).next()
