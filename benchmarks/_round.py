"""Round detection shared by the benchmark harnesses.

Artifacts freeze per round as ``<NAME>_r{NN}.json`` at the repo root;
the round being BUILT is one past the highest frozen round of ANY
family.  Deriving output names from this keeps a standalone harness run
from ever clobbering a frozen round's artifact.
"""

from __future__ import annotations

import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def current_round() -> int:
    rounds = [int(m.group(1)) for p in REPO.glob("*_r*.json")
              if (m := re.match(r"[A-Z0-9_]+_r(\d+)\.json", p.name))]
    return (max(rounds) + 1) if rounds else 1
