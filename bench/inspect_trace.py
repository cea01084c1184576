#!/usr/bin/env python3
"""Print the planes, lines and commonest event names of a profiler trace.

    python3 bench/inspect_trace.py <trace dir>

What to read before writing a reader against a trace: which planes are
devices, which are host threads, and how the programs and kernels are
named.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

if __name__ == "__main__":
    print(json.dumps(trace.overview(trace.find(Path(sys.argv[1]))), indent=1))
