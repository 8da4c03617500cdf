"""What a PR that ADDS a closed cell brings to the tests that hold the
closed cells, without editing them (the files the benchmark has are not
such a PR's to edit).

`test_bench_manifest.CLOSED_SIZES` is the table of the closed mixes'
sizes; `test_the_mixes_in_the_table_are_the_closed_mixes` holds that it
names EVERY closed mix of the manifest, and holds each row's callers,
answer and first step against the mix's file.  A mix that a later PR
adds gains its row here, once the module is collected, so that check
holds the new mix too; the three checks the table parametrises run on
the new row from the adding PR's own test file
(`test_bench_window_full.py`).  The next `benchmark` PR folds the rows
below into the table itself and empties this file.
"""

import sys

# traffic file -> its row; `tick`: the cell's tick in the window (chip)
MORE_CLOSED_SIZES = {
    # PR 51, `mimo25_mixed_closed_8k`
    "mixed_closed_8k_a512": dict(tick=0.25, slots=128, callers=192,
                                 answer=512, first_step=16),
}


def pytest_collection_modifyitems(session, config, items):
    held = sys.modules.get("test_bench_manifest")
    if held is not None:
        for name, row in MORE_CLOSED_SIZES.items():
            held.CLOSED_SIZES.setdefault(name, row)
