#!/usr/bin/env python
"""``python tools/file_seconds.py JUNIT.xml`` rewrites ``tests/file_seconds.json``:
test file -> seconds, the sum of its cases' ``time`` (set-up and tear-down
included) in the junit of one whole run of the tier-1 command.
``tests/conftest.py`` orders the files by it; no run of the tests writes it."""

import json
import sys
import xml.etree.ElementTree as ET
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def file_seconds(junit: str) -> dict[str, float]:
    seconds: dict[str, float] = {}
    for case in ET.parse(junit).iter("testcase"):
        parts = case.get("classname", "").split(".")  # tests.test_x[.TestClass]
        while parts and not (REPO / ("/".join(parts) + ".py")).is_file():
            parts.pop()
        if parts:  # a collection error's record names no file
            name = "/".join(parts) + ".py"
            seconds[name] = seconds.get(name, 0.0) + float(case.get("time", 0))
    return {name: round(s, 1) for name, s in sorted(seconds.items())}


if __name__ == "__main__":
    table = file_seconds(sys.argv[1])
    (REPO / "tests" / "file_seconds.json").write_text(json.dumps(table, indent=0) + "\n")
    print(f"{len(table)} files, {sum(table.values()):.0f} s")
