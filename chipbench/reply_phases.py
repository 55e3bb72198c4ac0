"""``chipbench/program_trace.py`` with the pending-reply buffer's scope.

Run from the root of a checkout, on a machine with a TPU::

    python3 chipbench/reply_phases.py --workload <cell> --seed <n>

The device program of a feedback cell names the pending-reply buffer's
work ``reply_admit``; ``program_trace.SCOPES`` predates it, so its
``scan_phases`` would count that work as ``unscoped``.  This runs
``program_trace`` with ``reply_admit`` among its scopes, and prints what
it prints.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_trace  # noqa: E402

#: the scopes ``program_trace`` reads, and the buffer's
SCOPES = program_trace.SCOPES + ("reply_admit",)


def main() -> None:
    program_trace.SCOPES = SCOPES
    program_trace.main()


if __name__ == "__main__":
    main()
