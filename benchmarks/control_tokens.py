#!/usr/bin/env python3
"""``control.py`` for a cell whose job brings its own control.

    python3 benchmarks/control_tokens.py --workload granite-h-micro-dense-level-8k --seeds 1,2,3 --seconds 5

``control.py`` puts the float8 ResNet reference in the program's place. A job
that compares against another reference hands its control on in what it
returns (``final["control_numbers"]``: the same comparisons, the reference
computed with float8 operands where the program was); this runs
``control.py``'s own loop and report over it.
"""

from __future__ import annotations

import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from benchmarks import control  # noqa: E402


def control_numbers(final: dict) -> dict:
    return final["control_numbers"](final)


def main(argv=None) -> int:
    control.control_numbers = control_numbers
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
