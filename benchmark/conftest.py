"""Standing exceptions among the benchmark's own tests: a test whose
assertion a later, contract-abiding change cannot satisfy and whose file
that change may not edit is expected to fail here, with the reason and the
test that checks the same thing in its place. A ``benchmark`` issue that
repairs the assertion deletes its entry."""

import pytest

EXPECTED_TO_FAIL = {
    "test_spans.py::test_every_new_metric_has_an_entry_and_a_reader": (
        "asserts that PR 24's seven metrics are the LAST entries of "
        "per_layer; new entries are appended at the end, so every metric "
        "added since fails it. test_lm_rehearsal.py::"
        "test_earlier_metrics_keep_their_entries_and_readers checks the "
        "same entries by name."
    ),
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, why in EXPECTED_TO_FAIL.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=why, strict=False))
