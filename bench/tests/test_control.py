"""The control of each cell, the plain reference one precision step below
the configuration's (covariance in bfloat16, solve at ``high``, queries
in bfloat16), put in the program's place: ``correct`` has to come out
false, while the program itself comes out true."""

import pytest


@pytest.mark.parametrize("cell", ["pca_1chip", "serve_refresh"])
def test_the_program_is_correct(run_tiny, cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["pca_1chip", "serve_refresh"])
def test_the_control_is_not(run_tiny, cell):
    out = run_tiny(cell, system="control")
    assert not out["correct"], out["checks"]
