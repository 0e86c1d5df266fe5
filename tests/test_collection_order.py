"""``tests/durations.json`` names every test file and nothing else: the order
of collection (``conftest.pytest_collection_modifyitems``) cannot go stale
unnoticed."""

import glob
import json
import os

import conftest

REGENERATE = ("python tools/test_durations.py JUNIT.xml  (the junit of a "
              "tier-1 run, or of the new file run alone: python -m pytest "
              "tests/test_new.py --junitxml=JUNIT.xml)")


def test_every_test_file_has_its_seconds_and_every_entry_a_file():
    with open(conftest.DURATIONS) as f:
        table = json.load(f)
    files = {os.path.basename(p) for p in glob.glob(
        os.path.join(os.path.dirname(conftest.DURATIONS), "test_*.py"))}
    assert set(table) == files, (
        f"tests/durations.json: no entry for {sorted(files - set(table))}, "
        f"no file for {sorted(set(table) - files)}; regenerate it: "
        + REGENERATE)
    assert all(isinstance(s, (int, float)) and s >= 0 for s in table.values())
