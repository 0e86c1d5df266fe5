#!/usr/bin/env python3
"""Write ``tests/durations.json``, the seconds each ``tests/test_*.py`` takes,
from a junit report.

    python tools/test_durations.py JUNIT.xml [JUNIT.xml ...]

``tests/conftest.py`` starts the files that take longest first (``--dist
loadfile`` hands files to the workers in collection order), and a tier-1 test
fails when a test file has no entry here or an entry has no file. The report is
that of a tier-1 run (``--junitxml``; the driver's command leaves it at
``/tmp/_t1.xml``) or of some files run alone: a file the reports do not cover
keeps the seconds it had, a new one that no report covers gets 0 and is
named, and entries whose file is gone are dropped. The file is written whole,
sorted by name.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import sys
import xml.etree.ElementTree as ET

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE = os.path.join(ROOT, "tests", "durations.json")


def seconds_by_file(junit_path):
    """{``test_x.py``: summed seconds of its test cases} of one report."""
    seconds = collections.Counter()
    for case in ET.parse(junit_path).iter("testcase"):
        parts = case.get("classname", "").split(".")
        name = next((p for p in parts if p.startswith("test_")), None)
        if name is not None and parts[0] == "tests":
            seconds[name + ".py"] += float(case.get("time") or 0.0)
    return seconds


def files_under_tests():
    return sorted(os.path.basename(p) for p in
                  glob.glob(os.path.join(ROOT, "tests", "test_*.py")))


def main(argv):
    if not argv:
        sys.exit(__doc__)
    try:
        with open(TABLE) as f:
            table = json.load(f)
    except FileNotFoundError:
        table = {}
    for path in argv:
        table.update(seconds_by_file(path))
    files = files_under_tests()
    unmeasured = [name for name in files if name not in table]
    table = {name: round(table.get(name, 0.0), 1) for name in files}
    with open(TABLE, "w") as f:
        json.dump(table, f, indent=0, sort_keys=True)
        f.write("\n")
    print(f"{TABLE}: {len(table)} files, {sum(table.values()):.0f} s")
    if unmeasured:
        print("no report covers (written as 0): " + ", ".join(unmeasured))


if __name__ == "__main__":
    main(sys.argv[1:])
