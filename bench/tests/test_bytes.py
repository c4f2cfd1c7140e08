"""Hand counts for the byte yardstick of the roofline shares."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bytes as workbytes  # noqa: E402


def test_ocean_field_stage3_stencils():
    # one 2400x3600 field: the int32 plane read once, then derivative0,
    # derivative1 and both gradient components written over the interior
    got = workbytes.request_bytes(("derivative0", "derivative1", "gradient"),
                                  1, (2400, 3600))
    assert got["read"] == 4 * 2400 * 3600 == 34_560_000
    assert got["write"] == 4 * 4 * 2398 * 3598 == 138_048_064


def test_ocean_field_laplacian():
    got = workbytes.request_bytes(("laplacian",), 1, (2400, 3600))
    assert got == {"read": 34_560_000, "write": 4 * 2398 * 3598}


def test_hurricane_variable_stats_and_laplacian():
    got = workbytes.request_bytes(("mean", "std", "laplacian"), 1,
                                  (100, 500, 500))
    assert got["read"] == 100_000_000
    assert got["write"] == 4 + 4 + 4 * 98 * 498 * 498 == 97_217_576


def test_thirteen_variables_and_coverage():
    one = workbytes.request_bytes(("mean", "std", "laplacian"), 1,
                                  (100, 500, 500))
    all13 = workbytes.request_bytes(("mean", "std", "laplacian"), 13,
                                    (100, 500, 500))
    assert all13 == {k: 13 * v for k, v in one.items()}
    assert workbytes.covered_bytes(13, (100, 500, 500)) == 1_300_000_000
    assert workbytes.covered_bytes(1, (20, 100, 100)) == 800_000
