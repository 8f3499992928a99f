"""One expected failure, stated where the run shows it.

``test_bm_files.py::test_the_median_time_per_token_is_judged_in_the_four_open_
loop_cells_and_no_other`` is PR 34's snapshot of ``end_to_end``: it counts the
open-loop cells and wants FOUR. PR 37 (``model_config``) adds the fifth,
``deepseek-v2.longctx``, and may add files to the benchmark but not edit one
that is there, so the count cannot be moved here. What the snapshot guarded
(bounds, sources, and that exactly the open-loop cells are judged on the
median time per token) is asserted for today's five cells in
``test_bm_latent.py``. ``strict``: the day a ``benchmark`` PR moves the count,
this shim fails the run and has to go.
"""

import pytest

SNAPSHOT = "test_the_median_time_per_token_is_judged_in_the_four_open_loop_cells_and_no_other"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == SNAPSHOT:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="PR 34's snapshot counts four open-loop cells; PR 37 adds the "
                                    "fifth and may not edit test_bm_files.py (test_bm_latent.py "
                                    "asserts the same for five)"))
