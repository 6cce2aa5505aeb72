"""Typed loader for the repo's frozen measurement artifacts.

The benchmarks freeze one JSON per family per round at the repo root —
``COMM_AUDIT_r08.json``, ``SCALING_MODEL_r05.json``,
``ROOFLINE_r18.json``, ``BENCH_SERVE_r09.json``, ... — in two physical
forms: a single JSON dict (most families) or JSONL rows
(``BENCH_SESSION``, ``BENCH_ADAPTER``).  This module is the ONE place
that knows how to find, parse, and validate them; the cost model only
ever sees :class:`Artifact` objects.

Selection and validation contract (the loud parts are deliberate):

- **newest round wins** per family; older rounds are recorded as
  ``superseded`` (not errors — history is supposed to accumulate).
- **declared metadata beats filename parsing**: artifacts written since
  the header convention landed carry ``{"artifact": {"schema", "family",
  "round", "geometry"}}`` (dict form: a top-level key; JSONL form: the
  first line).  A header that CONTRADICTS the filename means the file
  was renamed or hand-edited — rejected loudly, never trusted.
- **stale artifacts rejected loudly**: a family whose newest round
  trails the overall newest round by more than ``stale_rounds``
  (``TPUDIST_PLAN_STALE_ROUNDS``, default 20) no longer describes this
  codebase; it is rejected with a warning, and the cost model degrades
  to its analytic formula for that input — with an ``unmeasured`` flag
  in the plan report, never silently.
- **foreign geometry rejected loudly**: pass ``expect_geometry`` (e.g.
  ``{"n_devices": 8}``) and any artifact whose declared geometry
  contradicts it on an overlapping key is rejected.
- **missing families degrade, never raise** — unless ``strict``
  (``TPUDIST_PLAN_STRICT=1``), where :meth:`ArtifactSet.require`
  raises :class:`PlanArtifactError` naming what was rejected and why.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from tpudist.utils.envutil import env_flag, env_int

#: Header schema version this loader understands (satellite of ISSUE 20:
#: ``benchmarks/plan_bench.py`` writes it into its artifact's header).
ARTIFACT_SCHEMA = 1

#: Families the planner consumes.  Other frozen files (PARITY, BANDS,
#: MULTICHIP, ...) are evidence for humans, not cost-model inputs.
FAMILIES = (
    "SCALING_MODEL",
    "COMM_AUDIT",
    "ROOFLINE",
    "DECODE_PROFILE",
    "BENCH_SERVE",
    "BENCH_SESSION",
    "BENCH_ADAPTER",
    "PLAN",
)

#: Geometry keys compared for the foreign-geometry check.  Only keys
#: PRESENT ON BOTH SIDES are compared — an artifact that never declared
#: ``device_kind`` is not foreign to a query that does.
GEOMETRY_KEYS = ("platform", "n_devices", "device_kind")

_NAME_RE = re.compile(r"^([A-Z][A-Z0-9_]*?)_r(\d+)\.json$")


class PlanArtifactError(RuntimeError):
    """A required measurement artifact is missing or was rejected."""


@dataclasses.dataclass
class Rejection:
    path: Path
    reason: str


@dataclasses.dataclass
class Artifact:
    """One frozen measurement file, parsed and validated."""

    family: str
    round: int
    path: Path
    #: dict form: the parsed JSON object.  JSONL form: ``{"rows": [...]}``
    #: (header line, if any, lifted out into :attr:`header`).
    data: dict
    header: Optional[dict] = None

    @property
    def geometry(self) -> dict:
        """Declared geometry: header first, then the conventional
        top-level keys the older (pre-header) artifacts carry."""
        if self.header and isinstance(self.header.get("geometry"), dict):
            return dict(self.header["geometry"])
        out = {}
        for k in GEOMETRY_KEYS:
            if k in self.data:
                out[k] = self.data[k]
        g = self.data.get("geometry")
        if isinstance(g, dict):
            for k in GEOMETRY_KEYS:
                if k in g:
                    out.setdefault(k, g[k])
        return {k: v for k, v in out.items() if v is not None}

    @property
    def rows(self) -> List[dict]:
        r = self.data.get("rows")
        return r if isinstance(r, list) else []


@dataclasses.dataclass
class ArtifactSet:
    """Everything :func:`load_artifacts` found, kept, and refused."""

    root: Path
    by_family: Dict[str, Artifact]
    rejected: List[Rejection]
    superseded: List[Path]
    #: family → every VALID round, newest first (``by_family`` holds the
    #: head).  Sections that only older rounds measured are reachable
    #: through :meth:`section` without weakening newest-round-wins for
    #: anything the newest round does carry.
    history: Dict[str, List[Artifact]] = dataclasses.field(
        default_factory=dict)

    def get(self, family: str) -> Optional[Artifact]:
        return self.by_family.get(family)

    def section(self, family: str, key: str
                ) -> Tuple[Optional[object], Optional[int]]:
        """Newest round of ``family`` that MEASURED section ``key``.

        Benchmark rounds are not supersets of each other (r18 froze the
        kernel twins, r09 the spec sweep) — "newest round wins" means
        the newest round that actually measured the thing.  Returns
        ``(value, round)`` or ``(None, None)``."""
        for a in self.history.get(family, []):
            v = a.data.get(key)
            if v not in (None, {}, []):
                return v, a.round
        return None, None

    def require(self, family: str) -> Artifact:
        a = self.by_family.get(family)
        if a is None:
            why = "; ".join(
                f"{r.path.name}: {r.reason}" for r in self.rejected
                if r.path.name.startswith(family + "_r")) or "no file found"
            raise PlanArtifactError(
                f"required artifact family {family!r} unavailable under "
                f"{self.root} ({why}) — run the family's harness "
                f"under benchmarks/ or unset "
                f"TPUDIST_PLAN_STRICT to degrade to the analytic model")
        return a

    def rounds(self) -> Dict[str, int]:
        """family → round actually loaded (the provenance line every
        plan report quotes)."""
        return {f: a.round for f, a in sorted(self.by_family.items())}

    def missing(self, families: Sequence[str]) -> List[str]:
        return [f for f in families if f not in self.by_family]


def default_root() -> Path:
    """Artifact directory: ``TPUDIST_PLAN_DIR`` else the repo root (the
    directory the benchmarks freeze into)."""
    env = os.environ.get("TPUDIST_PLAN_DIR")
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[2]


def _parse(path: Path) -> Tuple[dict, Optional[dict]]:
    """Parse either physical form; return ``(data, header)``."""
    text = path.read_text()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict):
        header = obj.get("artifact")
        return obj, header if isinstance(header, dict) else None
    if isinstance(obj, list):
        return {"rows": obj}, None
    # JSONL: one object per line; an optional leading header line
    rows = []
    header = None
    for i, line in enumerate(text.splitlines()):
        line = line.strip()
        if not line:
            continue
        row = json.loads(line)
        if i == 0 and isinstance(row, dict) and isinstance(
                row.get("artifact"), dict) and len(row) == 1:
            header = row["artifact"]
            continue
        rows.append(row)
    return {"rows": rows}, header


def geometry_conflicts(declared: dict, expected: dict) -> List[str]:
    """Keys present on BOTH sides with contradicting values."""
    out = []
    for k in GEOMETRY_KEYS:
        if k in declared and k in expected and declared[k] != expected[k]:
            out.append(f"{k}={declared[k]!r} (expected {expected[k]!r})")
    return out


def load_artifacts(
    root: "str | Path | None" = None,
    *,
    families: Sequence[str] = FAMILIES,
    expect_geometry: Optional[dict] = None,
    stale_rounds: Optional[int] = None,
    strict: Optional[bool] = None,
) -> ArtifactSet:
    """Scan ``root`` for ``<FAMILY>_rNN.json`` and build the set.

    Every refusal lands in ``rejected`` AND raises a ``UserWarning`` —
    a planner silently ignoring evidence would be worse than no planner.
    ``strict`` (default ``TPUDIST_PLAN_STRICT``) additionally makes
    :meth:`ArtifactSet.require` the access path callers should use.
    """
    root = Path(root) if root is not None else default_root()
    if stale_rounds is None:
        stale_rounds = env_int("TPUDIST_PLAN_STALE_ROUNDS", 20)
    if strict is None:
        strict = env_flag("TPUDIST_PLAN_STRICT", False)

    found: Dict[str, List[Tuple[int, Path]]] = {}
    for p in sorted(root.glob("*_r*.json")):
        m = _NAME_RE.match(p.name)
        if not m or m.group(1) not in families:
            continue
        found.setdefault(m.group(1), []).append((int(m.group(2)), p))

    newest_overall = max(
        (r for cands in found.values() for r, _ in cands), default=0)

    rejected: List[Rejection] = []
    superseded: List[Path] = []
    by_family: Dict[str, Artifact] = {}
    history: Dict[str, List[Artifact]] = {}

    def _reject(path: Path, reason: str) -> None:
        rejected.append(Rejection(path=path, reason=reason))
        warnings.warn(
            f"tpudist.plan: rejected artifact {path.name}: {reason}",
            stacklevel=3)

    for family, cands in found.items():
        # newest round wins; walk downward so a rejected newest round
        # falls back to the next one (still loudly).  Valid older
        # rounds stay reachable through ArtifactSet.section.
        for rnd, path in sorted(cands, reverse=True):
            if newest_overall - rnd > stale_rounds:
                _reject(path, f"stale: round r{rnd:02d} trails newest "
                              f"r{newest_overall:02d} by more than "
                              f"{stale_rounds} rounds "
                              f"(TPUDIST_PLAN_STALE_ROUNDS)")
                continue
            try:
                data, header = _parse(path)
            except (json.JSONDecodeError, OSError) as e:
                _reject(path, f"unparseable: {e}")
                continue
            if header is not None:
                hfam, hrnd = header.get("family"), header.get("round")
                if hfam is not None and hfam != family:
                    _reject(path, f"declared family {hfam!r} contradicts "
                                  f"filename family {family!r}")
                    continue
                if hrnd is not None and int(hrnd) != rnd:
                    _reject(path, f"declared round r{int(hrnd):02d} "
                                  f"contradicts filename round r{rnd:02d}")
                    continue
                hschema = header.get("schema")
                if hschema is not None and int(hschema) > ARTIFACT_SCHEMA:
                    _reject(path, f"schema {hschema} is newer than this "
                                  f"loader understands "
                                  f"({ARTIFACT_SCHEMA})")
                    continue
            art = Artifact(family=family, round=rnd, path=path,
                           data=data, header=header)
            if expect_geometry:
                conflicts = geometry_conflicts(art.geometry, expect_geometry)
                if conflicts:
                    _reject(path,
                            "foreign geometry: " + ", ".join(conflicts))
                    continue
            if family in by_family:
                superseded.append(path)
            else:
                by_family[family] = art
            history.setdefault(family, []).append(art)

    out = ArtifactSet(root=root, by_family=by_family,
                      rejected=rejected, superseded=superseded,
                      history=history)
    if strict:
        missing = out.missing(families)
        if missing:
            # strict callers want the failure at load time, not at the
            # first degraded estimate
            raise PlanArtifactError(
                f"TPUDIST_PLAN_STRICT: missing artifact families "
                f"{missing} under {root}")
    return out
