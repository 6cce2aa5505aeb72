"""These tests run on the CPU (``JAX_PLATFORMS=cpu pytest cellbench/tests``);
four virtual devices come from ``XLA_FLAGS`` set before jax first loads."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPUDIST_COMPILATION_CACHE", "off")
os.environ.setdefault("TPUDIST_TELEMETRY", "0")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")


import gzip
import json
import shutil
from pathlib import Path

import pytest

from cellbench import archs

DATA = Path(__file__).resolve().parent / "data"
#: a second architecture added as files alone, laid out as ``cellbench/``
#: itself is (``archs/``, ``configs/``, ``workloads/``); its module is found
#: as ``cellbench.archs.<model_type>``, where a later PR's will be
FILES_ALONE = DATA / "files_alone"
archs.__path__.append(str(FILES_ALONE / "archs"))
FILES_ALONE_CELLS = sorted(
    p.stem for p in (FILES_ALONE / "workloads").glob("*.json"))


def load_cell(name: str) -> tuple:
    """``(cell, config)`` of a test cell: the GPT-2-shaped ones lie flat
    under ``data/``, the files-alone ones under ``data/files_alone/``."""
    flat = DATA / f"{name}.json"
    cell = json.loads((flat if flat.is_file() else
                       FILES_ALONE / "workloads" / f"{name}.json").read_text())
    cell["name"] = name
    flat = DATA / f"{cell['config']}.json"
    config = json.loads((flat if flat.is_file() else FILES_ALONE / "configs"
                         / f"{cell['config']}.json").read_text())
    return cell, config


def unpacked(tmp_path_factory, directory: str, file: str) -> Path:
    """A recorded trace unpacked where ``trace_reduce.find_xplane`` looks."""
    root = tmp_path_factory.mktemp(directory)
    out = root / "plugins" / "profile" / "recorded"
    out.mkdir(parents=True)
    with gzip.open(DATA / directory / f"{file}.gz", "rb") as f, \
            open(out / file, "wb") as g:
        shutil.copyfileobj(f, g)
    return root


@pytest.fixture(scope="session")
def recorded_trace_dir(tmp_path_factory):
    """The trace cut from the first traced four-chip run (PR 24; chips 0 and
    1, two whole steps, op lines + the runner's host spans), from before the
    program named its kernels and scopes."""
    return unpacked(tmp_path_factory, "trace_fsdp4", "fsdp4.xplane.pb")


@pytest.fixture(scope="session")
def scoped_trace_dir(tmp_path_factory):
    """The trace cut from the first traced run of ``cgpt590m-train-1chip``
    with the program's names in it (PR 25; chip 0, two whole steps)."""
    return unpacked(tmp_path_factory, "trace_1chip_scoped", "1chip.xplane.pb")
