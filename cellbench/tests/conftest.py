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
import shutil
from pathlib import Path

import pytest


@pytest.fixture(scope="session")
def recorded_trace_dir(tmp_path_factory):
    """The trace cut from the first traced four-chip run (PR 24; chips 0 and
    1, two whole steps, op lines + the runner's host spans), unpacked where
    ``trace_reduce.find_xplane`` looks."""
    src = (Path(__file__).resolve().parent / "data" / "trace_fsdp4"
           / "fsdp4.xplane.pb.gz")
    root = tmp_path_factory.mktemp("trace_fsdp4")
    out = root / "plugins" / "profile" / "recorded"
    out.mkdir(parents=True)
    with gzip.open(src, "rb") as f, open(out / "fsdp4.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    return root
