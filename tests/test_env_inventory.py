"""Env-var inventory gate: every ``TPUDIST_*`` knob referenced anywhere
in the package must be registered in ``tpudist.utils.envutil.ENV_VARS``
(the one parse/inventory module) and documented in
``docs/ARCHITECTURE.md`` — so a new knob (telemetry's included) cannot
ship undocumented."""

import re
from pathlib import Path

from tpudist.utils import envutil

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "tpudist"
DOCS = REPO / "docs" / "ARCHITECTURE.md"

#: Matches full names (TPUDIST_WATCHDOG_S) and wildcard/prefix mentions
#: (``TPUDIST_SERVE_*`` or an f-string ``TPUDIST_{key}`` construction,
#: which surface as a trailing-underscore token).
_TOKEN = re.compile(r"TPUDIST_[A-Z0-9_]*")


def _scan_package():
    names, prefixes = set(), set()
    for path in PKG.rglob("*.py"):
        if path == PKG / "utils" / "envutil.py":
            continue  # the registry itself must not self-satisfy the gate
        for tok in _TOKEN.findall(path.read_text()):
            if tok.endswith("_"):
                prefixes.add(tok)  # wildcard mention: TPUDIST_SERVE_*
            else:
                names.add(tok)
    return names, prefixes


def test_every_referenced_var_is_registered():
    names, _ = _scan_package()
    unregistered = sorted(names - envutil.ENV_VARS.keys())
    assert not unregistered, (
        f"TPUDIST_* env vars referenced in the package but missing from "
        f"tpudist.utils.envutil.ENV_VARS (add the entry + a row in "
        f"docs/ARCHITECTURE.md): {unregistered}")


def test_every_registered_var_is_documented():
    text = DOCS.read_text()
    undocumented = sorted(v for v in envutil.ENV_VARS if v not in text)
    assert not undocumented, (
        f"ENV_VARS entries missing from docs/ARCHITECTURE.md's "
        f"environment-knob table: {undocumented}")


def test_no_stale_registry_entries():
    """Every registered name is actually consumed by the package — by
    literal token or through a wildcard construction site prefix."""
    names, prefixes = _scan_package()
    # A bare ``TPUDIST_`` construction prefix would make every entry pass;
    # only count specific prefixes.
    specific = {p for p in prefixes if p != "TPUDIST_"}
    stale = sorted(
        v for v in envutil.ENV_VARS
        if v not in names and not any(v.startswith(p) for p in specific))
    assert not stale, (
        f"ENV_VARS entries no longer referenced anywhere in the package "
        f"(remove them or wire them back up): {stale}")


def test_registry_descriptions_nonempty():
    for name, desc in envutil.ENV_VARS.items():
        assert name.startswith("TPUDIST_")
        assert isinstance(desc, str) and len(desc) >= 8, (
            f"{name}: the registry entry needs a real one-line contract")
