"""The benchmark's copied generators reproduce the program's bit for bit."""

import numpy as np
import pytest

from bench import generators
from repro.core import build_app

FIELDS = ("n_neurons", "pre", "post", "weight", "spikes", "layer_of", "name")


def same(snn, fields):
    for f in FIELDS:
        a, b = getattr(snn, f), fields[f]
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype and np.array_equal(a, b), f
        else:
            assert a == b, f


@pytest.mark.parametrize("name", ["MLP-MNIST", "ImgSmooth", "EdgeDet"])
def test_table1_apps_match_program(name):
    same(build_app(name), generators.build_app(name))
