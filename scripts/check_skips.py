#!/usr/bin/env python
"""Skip-count gate (CI tier1 job).

With the pinned stack (requirements-dev.txt) no test skips: hypothesis is
installed and libtpu can describe the v5e topology the compile-only tests
need.  Skips must not silently appear: a new `pytest.importorskip` or
capability guard that starts skipping real coverage fails CI until the
ceiling here is consciously raised.

    python scripts/check_skips.py pytest-results.xml --max-skips 0
"""
from __future__ import annotations

import argparse
import sys
import xml.etree.ElementTree as ET


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("junit_xml", help="pytest --junitxml output")
    p.add_argument("--max-skips", type=int, default=0)
    args = p.parse_args()

    root = ET.parse(args.junit_xml).getroot()
    suites = [root] if root.tag == "testsuite" else list(
        root.iter("testsuite"))
    skipped = sum(int(s.get("skipped", 0)) for s in suites)

    for case in root.iter("testcase"):
        sk = case.find("skipped")
        if sk is not None:
            name = f"{case.get('classname', '?')}::{case.get('name', '?')}"
            print(f"skipped  {name}: {sk.get('message', '')[:120]}")

    if skipped > args.max_skips:
        print(f"\n{skipped} tests skipped, ceiling is {args.max_skips} — "
              f"a capability skip crept in; fix it or consciously raise "
              f"the ceiling in .github/workflows/ci.yml", file=sys.stderr)
        sys.exit(1)
    print(f"\n{skipped} skip(s) <= ceiling {args.max_skips}")


if __name__ == "__main__":
    main()
