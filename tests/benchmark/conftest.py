"""Three assertions of the accepted benchmark's tests pin `BENCHMARK.json` as
PR 31 left it (five cells, `mla_core_device_ms.train` the last per-layer
entry, `mfu_pct.tput` in one cell), and a PR that adds a cell at the end of
the lists may not edit the files they stand in. They are expected to fail,
strictly, until a `benchmark` PR rewrites them; what each held of the
manifest as it is now, `test_ling_cell.py` asserts by name."""

import pytest

PINNED_TO_AN_OLDER_MANIFEST = {
    "test_glm_cells.py::test_the_manifest_lists_the_new_cells_and_their_metrics":
        "asserts five cells; PR 33 added the sixth",
    "test_mla_core_metric.py::"
    "test_the_manifest_lists_the_core_metric_in_the_decoder_cell_alone":
        "reads the entry as per_layer[-1]; PR 33 appended eleven after it",
    "test_serve_classes.py::test_the_four_metrics_of_pr_27_are_listed_with_a_"
    "reader_each[mfu_pct.tput-serve-base-sat]":
        "asserts one cell; PR 33 appended its cell to mfu_pct.tput's list",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, why in PINNED_TO_AN_OLDER_MANIFEST.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))
