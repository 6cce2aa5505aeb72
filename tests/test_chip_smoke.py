"""``chip_smoke.py`` rehearsed on the CPU: every phase's function runs at a
tiny size with the kernels interpreted, so wrong paths, arguments and
control flow are found here and not on the chip — and the script itself
refuses to report anything without a TPU.

Nothing here is a chip run: the sizes are toy sizes, the Pallas kernels
run in the interpreter, and the compiled programs hold no Mosaic call
(``expect_kernel=False``).  What the chip's compiler accepts is
``tests/test_chip_compile.py``'s business; results and times are the
chip's.
"""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = chip_smoke.TINY


def _run_script(cwd: Path, *argv):
    return subprocess.run(
        [sys.executable, str(cwd / "chip_smoke.py"), *argv], cwd=cwd,
        capture_output=True, text=True, timeout=300)


def _reports_ok(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    try:
        return bool(lines) and json.loads(lines[-1]).get("ok") is True
    except ValueError:
        return False


class TestRefusesWithoutTheChip:
    @pytest.mark.parametrize("argv", [(), ("--chips", "4")],
                             ids=["one-chip", "four-chips"])
    def test_no_accelerator_exits_nonzero_and_prints_no_result(self, argv):
        """conftest pins this process tree to the CPU: the script must
        say so and stop, not fall back."""
        proc = _run_script(REPO, *argv)
        assert proc.returncode != 0
        assert not _reports_ok(proc.stdout)
        assert "no TPU" in proc.stderr

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        """A directory that holds chip_smoke.py and nothing else of the
        repo: there is no program to prove."""
        shutil.copy(REPO / "chip_smoke.py", tmp_path)
        proc = _run_script(tmp_path)
        assert proc.returncode != 0
        assert not _reports_ok(proc.stdout)

    def test_sets_no_platform_and_starts_no_child(self):
        """One process for the chip; JAX picks the device, not the
        script."""
        src = (REPO / "chip_smoke.py").read_text()
        for needle in ("JAX_PLATFORMS", "jax_platforms", "subprocess",
                       "multiprocessing", "os.fork"):
            assert needle not in src, needle

    def test_unknown_device_kind_is_an_error(self):
        class Dev:
            platform, device_kind = "tpu", "TPU v99 imaginary"

        with pytest.raises(AssertionError, match="peaks table"):
            chip_smoke.describe_device(Dev(), 1)


class TestPhasesTiny:
    def test_train(self):
        losses = chip_smoke.phase_train(TINY, seed=0, expect_kernel=False)
        assert len(losses) == 10 and losses[-1] < losses[0]

    def test_train_asserts_the_kernel_is_in_the_program(self):
        """On the CPU attention takes the XLA formulation — exactly the
        silent route the chip run must refuse."""
        with pytest.raises(AssertionError, match="tpu_custom_call"):
            chip_smoke.phase_train(TINY, seed=0, steps=1)

    def test_demo(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)  # the demo logs under ./runs
        curve = chip_smoke.phase_demo(seed=0, iterations=300)
        assert set(curve) == {"model_X", "model_Y"}

    def test_serve(self):
        handles = chip_smoke.phase_serve(TINY, seed=0, max_new_cap=12)
        assert len(handles) == 8
        assert all(h.finish_reason == "length" for h in handles)

    def test_kernels(self):
        report = chip_smoke.phase_kernels(TINY, seed=0, interpret=True)
        families = {name.split("/")[0] for name in report}
        assert families >= {
            "numerics_gate", "paged_attention", "paged_prefill",
            "fused_sample", "fused_residual", "fused_rope_qkv",
            "lora_delta"}
        assert all(np.isfinite(v) for k, v in report.items()
                   if k != "numerics_gate")

    def test_kernel_check_catches_a_wrong_kernel(self):
        case = next(c for c in chip_smoke.kernel_cases(TINY)
                    if c.name.startswith("lora_delta"))
        args = case.make(np.random.default_rng(0))
        ref = case.ref(*args)
        chip_smoke.check_case(case, ref, ref)
        with pytest.raises(AssertionError, match="lora_delta"):
            chip_smoke.check_case(case, ref * 1.5, ref)

    def test_multichip(self, devices):
        """Both meshes against one device, on 4 of the virtual devices
        (a tiny model replicates its small leaves, hence the share)."""
        chip_smoke.phase_multichip(TINY, seed=0, interpret=True,
                                   max_share=0.6)

    def test_multichip_catches_a_wrong_update(self, devices, monkeypatch):
        monkeypatch.setattr(chip_smoke, "update_error",
                            lambda p0, ref, got: 0.5)
        with pytest.raises(AssertionError, match="parameter updates"):
            chip_smoke.phase_multichip(TINY, seed=0, interpret=True,
                                       max_share=0.6)
