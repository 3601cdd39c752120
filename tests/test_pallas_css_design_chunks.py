"""The CSS kernels with a shared design (ISSUE 51) past ONE time chunk: 2,048
steps are two chunks of 1,024, so the design's block moves with the chunk
index, the forward's AR lag reads take ``u`` of the chunk before from its
carry (no neighbour block of ``y`` would do: the residual is not in HBM),
and the adjoint's ``-x' dS/du`` accumulates over both chunks in the revisited
gradient block.  The one-chunk cases and what the checks compare:
``tests/test_pallas_css_design.py`` / ``_pallas_helpers._check_fused_design``.
"""

import pytest

from _pallas_helpers import _check_design_entry, _check_fused_design


@pytest.mark.parametrize("r", [1, 2, 4])
@pytest.mark.parametrize("k", [1, 31])
def test_fused_design_crosses_time_chunks(k, r):
    _check_fused_design(k, r, 2048)


def test_entry_crosses_time_chunks():
    _check_design_entry(1100)  # a padded second chunk
