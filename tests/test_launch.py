"""Launch-layer tests: tpurun agent (spawn/env-contract/restart/crash
records), data staging, and sweep expansion.

The reference verified its launcher only by manual cluster runs (SURVEY.md
§4); here the agent is exercised for real with subprocess worker groups on
CPU.  True multi-process rendezvous (jax.distributed over localhost) is in
``test_multiprocess.py``.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tpudist.launch.run import main as tpurun_main
from tpudist.launch.staging import create_tarball, extract_tarballs
from tpudist.launch.sweep import SweepSpec

REPO = Path(__file__).resolve().parent.parent


def _write_worker(tmp_path: Path, body: str) -> Path:
    p = tmp_path / "worker.py"
    p.write_text(textwrap.dedent(body))
    return p


def _clean_env(monkeypatch):
    for var in list(os.environ):
        if var.startswith("TPUDIST_") or var in ("RANK", "WORLD_SIZE", "MASTER_ADDR"):
            monkeypatch.delenv(var, raising=False)


class TestTpurun:
    def test_env_contract(self, tmp_path, monkeypatch):
        """Workers see the full TPUDIST_* contract with correct ranks."""
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import json, os, sys
            keys = ["TPUDIST_NUM_PROCESSES", "TPUDIST_PROCESS_ID",
                    "TPUDIST_LOCAL_RANK", "TPUDIST_LOCAL_WORLD_SIZE",
                    "TPUDIST_COORDINATOR", "TPUDIST_RUN_ID", "TPUDIST_TMPDIR"]
            rec = {k: os.environ.get(k) for k in keys}
            out = os.path.join(os.environ["OUT_DIR"],
                               f"rank{rec['TPUDIST_PROCESS_ID']}.json")
            json.dump(rec, open(out, "w"))
        """)
        out_dir = tmp_path / "out"
        out_dir.mkdir()
        monkeypatch.setenv("OUT_DIR", str(out_dir))
        rc = tpurun_main(["--nprocs", "3", "--tmpdir", str(tmp_path / "scratch"),
                          "--", sys.executable, str(worker)])
        assert rc == 0
        recs = {json.load(open(f))["TPUDIST_PROCESS_ID"]: json.load(open(f))
                for f in out_dir.glob("rank*.json")}
        assert sorted(recs) == ["0", "1", "2"]
        for rank, rec in recs.items():
            assert rec["TPUDIST_NUM_PROCESSES"] == "3"
            assert rec["TPUDIST_LOCAL_RANK"] == rank
            assert rec["TPUDIST_LOCAL_WORLD_SIZE"] == "3"
            assert rec["TPUDIST_COORDINATOR"].startswith("127.0.0.1:")

    def test_devices_per_proc_sets_xla_flag(self, tmp_path, monkeypatch):
        """--devices-per-proc plants the host-platform device-count flag
        in each worker's XLA_FLAGS (replacing any inherited one), so CPU
        rungs can run per-process multi-device meshes; without the flag
        the inherited env passes through untouched."""
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import json, os
            out = os.path.join(os.environ["OUT_DIR"],
                               "r" + os.environ["TPUDIST_PROCESS_ID"]
                               + ".json")
            json.dump({"xla": os.environ.get("XLA_FLAGS", "")},
                      open(out, "w"))
        """)
        out_dir = tmp_path / "out"
        out_dir.mkdir()
        monkeypatch.setenv("OUT_DIR", str(out_dir))
        # a stale inherited count must be REPLACED, not duplicated
        monkeypatch.setenv(
            "XLA_FLAGS",
            "--xla_foo=1 --xla_force_host_platform_device_count=3")
        rc = tpurun_main(["--nprocs", "2", "--devices-per-proc", "4",
                          "--tmpdir", str(tmp_path / "scratch"),
                          "--", sys.executable, str(worker)])
        assert rc == 0
        recs = [json.load(open(f)) for f in sorted(out_dir.glob("r*.json"))]
        assert len(recs) == 2
        for rec in recs:
            assert rec["xla"].count(
                "xla_force_host_platform_device_count") == 1
            assert "--xla_force_host_platform_device_count=4" in rec["xla"]
            assert "--xla_foo=1" in rec["xla"]  # other flags preserved

    def test_node_rank_offsets_global_rank(self, tmp_path, monkeypatch):
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import os, pathlib
            pathlib.Path(os.environ["OUT_DIR"],
                         "g" + os.environ["TPUDIST_PROCESS_ID"]).touch()
        """)
        out_dir = tmp_path / "out"
        out_dir.mkdir()
        monkeypatch.setenv("OUT_DIR", str(out_dir))
        rc = tpurun_main(["--nprocs", "2", "--nnodes", "2", "--node-rank", "1",
                          "--coordinator", "127.0.0.1:12399",
                          "--tmpdir", str(tmp_path / "s"),
                          "--", sys.executable, str(worker)])
        assert rc == 0
        assert sorted(p.name for p in out_dir.iterdir()) == ["g2", "g3"]

    def test_restart_then_success(self, tmp_path, monkeypatch):
        """A worker that fails on attempt 0 and succeeds on attempt 1:
        tpurun must restart the group (torchrun --max_restarts parity) and
        exit 0, leaving a crash record from the first attempt."""
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import os
            from tpudist.utils.record import record

            @record
            def main():
                if os.environ["TPUDIST_RESTART_COUNT"] == "0":
                    raise RuntimeError("injected first-attempt failure")

            main()
        """)
        err_dir = tmp_path / "errors"
        monkeypatch.setenv("PYTHONPATH", str(REPO))
        rc = tpurun_main(["--nprocs", "2", "--max-restarts", "2",
                          "--restart-backoff", "0.05",
                          "--tmpdir", str(tmp_path / "s"),
                          "--error-dir", str(err_dir),
                          "--", sys.executable, str(worker)])
        assert rc == 0
        records = list(err_dir.glob("error_attempt0_rank*.json"))
        assert records, "first attempt must leave crash records"
        rec = json.load(open(records[0]))
        assert rec["exc_type"] == "RuntimeError"
        assert "injected" in rec["message"]

    def test_exhausted_restarts_fail(self, tmp_path, monkeypatch):
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, "raise SystemExit(7)\n")
        rc = tpurun_main(["--nprocs", "1", "--max-restarts", "1",
                          "--restart-backoff", "0.01",
                          "--tmpdir", str(tmp_path / "s"),
                          "--", sys.executable, str(worker)])
        assert rc == 1

    def test_elastic_relaunches_at_surviving_world(self, tmp_path,
                                                   monkeypatch):
        """--elastic survivor relaunch, end to end through the agent: a
        rank that dies at world 2 exhausts the (zero) restart budget →
        the group relaunches at world 1 with a fresh budget and a
        monotone generation, the dead rank named from the agent's own
        exit observation (a SIGKILLed worker leaves no crash record),
        and the exhaustion + resize land in the agent's telemetry
        stream for the merged report."""
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import json, os, sys, time
            world = int(os.environ["TPUDIST_NUM_PROCESSES"])
            rank = int(os.environ["TPUDIST_PROCESS_ID"])
            if world > 1:
                if rank == 1:
                    sys.exit(9)      # the dying rank
                time.sleep(30)       # survivor: terminated by the agent
                sys.exit(0)
            with open(os.path.join(os.environ["OUT_DIR"],
                                   f"ok{rank}.json"), "w") as f:
                json.dump({"world": world,
                           "gen": os.environ["TPUDIST_RESTART_COUNT"]}, f)
        """)
        out_dir = tmp_path / "out"
        out_dir.mkdir()
        tele_dir = tmp_path / "tele"
        monkeypatch.setenv("OUT_DIR", str(out_dir))
        rc = tpurun_main(["--nprocs", "2", "--max-restarts", "0",
                          "--elastic", "--restart-backoff", "0.05",
                          "--tmpdir", str(tmp_path / "scratch"),
                          "--telemetry-dir", str(tele_dir),
                          "--", sys.executable, str(worker)])
        assert rc == 0
        ok = json.load(open(out_dir / "ok0.json"))
        assert ok == {"world": 1, "gen": "1"}  # resized, gen monotone
        assert not (out_dir / "ok1.json").exists()
        # agent stream (pseudo-rank = initial world + node_rank = 2):
        # exhaustion stamped, then the resize with the observed dead rank
        recs = [json.loads(l) for l in
                (tele_dir / "rank2_gen0.jsonl").read_text().splitlines()]
        names = [r["name"] for r in recs]
        assert "restart_exhausted" in names and "world_resized" in names
        ex = next(r for r in recs if r["name"] == "restart_exhausted")
        assert ex["world"] == 2 and ex["attempts"] == 1
        assert ex["dead_ranks"] == [1]
        rs = next(r for r in recs if r["name"] == "world_resized")
        assert rs["from_world"] == 2 and rs["to_world"] == 1
        assert rs["dead_ranks"] == [1]

    def test_elastic_world_one_exhaustion_gives_up(self, tmp_path,
                                                   monkeypatch):
        """Elastic cannot shrink below 1: exhaustion at world 1 is the
        end of the line (rc 1, restart_exhausted still stamped)."""
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import sys
            sys.exit(3)
        """)
        tele_dir = tmp_path / "tele"
        rc = tpurun_main(["--nprocs", "1", "--max-restarts", "0",
                          "--elastic", "--restart-backoff", "0.05",
                          "--tmpdir", str(tmp_path / "scratch"),
                          "--telemetry-dir", str(tele_dir),
                          "--", sys.executable, str(worker)])
        assert rc == 1
        recs = [json.loads(l) for l in
                (tele_dir / "rank1_gen0.jsonl").read_text().splitlines()]
        assert any(r["name"] == "restart_exhausted" and r["world"] == 1
                   for r in recs)
        assert not any(r["name"] == "world_resized" for r in recs)

    def test_restart_exhausted_event_without_elastic(self, tmp_path,
                                                     monkeypatch):
        """The satellite: exhaustion is no longer stderr-only — the
        fixed-size path stamps restart_exhausted into the telemetry the
        merged report reads."""
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import sys
            sys.exit(7)
        """)
        tele_dir = tmp_path / "tele"
        rc = tpurun_main(["--nprocs", "2", "--max-restarts", "1",
                          "--restart-backoff", "0.05",
                          "--tmpdir", str(tmp_path / "scratch"),
                          "--telemetry-dir", str(tele_dir),
                          "--", sys.executable, str(worker)])
        assert rc == 1
        recs = [json.loads(l) for l in
                (tele_dir / "rank2_gen0.jsonl").read_text().splitlines()]
        ex = next(r for r in recs if r["name"] == "restart_exhausted")
        assert ex["attempts"] == 2 and ex["world"] == 2

    def test_elastic_requires_single_node(self):
        with pytest.raises(SystemExit, match="elastic"):
            tpurun_main(["--nnodes", "2", "--node-rank", "0",
                         "--coordinator", "h:1", "--elastic",
                         "--", "python", "x.py"])

    @pytest.mark.parametrize("env,refused", [
        ({}, True),                                   # every worker, every chip
        ({"TPU_VISIBLE_CHIPS": "0"}, False),          # caller bound the chips
        ({"JAX_PLATFORMS": "cpu"}, False),            # workers stay off the TPU
        ({"JAX_PLATFORMS": "tpu,cpu"}, True),
    ], ids=["unbound", "tpu-visible", "cpu-pinned", "tpu-listed"])
    def test_nprocs_on_tpu_host_needs_chip_binding(self, tmp_path,
                                                   monkeypatch, env,
                                                   refused):
        """One process for each chip: N>1 workers on a (faked) TPU host
        are refused before anything is spawned, unless bound."""
        _clean_env(monkeypatch)
        for var in list(os.environ):
            if var == "JAX_PLATFORMS" or (
                    var.startswith("TPU_") and "VISIBLE" in var):
                monkeypatch.delenv(var)
        monkeypatch.setenv("TPU_NAME", "fake-v5e")
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        marker = tmp_path / "ran"
        worker = _write_worker(tmp_path, f"""
            import os
            open({str(marker)!r} + os.environ["TPUDIST_PROCESS_ID"], "w")
        """)
        argv = ["--nprocs", "2", "--tmpdir", str(tmp_path / "scratch"),
                "--", sys.executable, str(worker)]
        if refused:
            with pytest.raises(SystemExit, match="chip binding"):
                tpurun_main(argv)
            assert not list(tmp_path.glob("ran*"))
        else:
            assert tpurun_main(argv) == 0
            assert len(list(tmp_path.glob("ran*"))) == 2
        # one worker per host is always fine
        assert tpurun_main(["--nprocs", "1"] + argv[2:]) == 0

    def test_agent_import_initializes_no_backend(self):
        """The agent must stay off the chip its workers need."""
        code = ("import tpudist.launch.run, jax._src.xla_bridge as xb; "
                "assert not xb.backends_are_initialized()")
        subprocess.run([sys.executable, "-c", code], check=True,
                       cwd=Path(__file__).resolve().parent.parent)

    def test_cmd_must_start_with_python(self, tmp_path):
        # torchrun_launcher.sh:23-25 parity.
        with pytest.raises(SystemExit):
            tpurun_main(["--nprocs", "1", "--", "bash", "-c", "true"])

    def test_peer_workers_killed_on_failure(self, tmp_path, monkeypatch):
        """When one rank dies the agent terminates the rest of the group
        promptly instead of waiting out a hung job."""
        _clean_env(monkeypatch)
        worker = _write_worker(tmp_path, """
            import os, sys, time
            if os.environ["TPUDIST_PROCESS_ID"] == "0":
                sys.exit(3)
            time.sleep(120)   # would hang without group termination
        """)
        import time
        t0 = time.time()
        rc = tpurun_main(["--nprocs", "2", "--max-restarts", "0",
                          "--tmpdir", str(tmp_path / "s"),
                          "--", sys.executable, str(worker)])
        assert rc == 1
        assert time.time() - t0 < 60


class TestTerminate:
    """`_terminate`'s grace window: SIGTERM first, SIGKILL escalation only
    after `grace_s` (satellite coverage — the window is what lets workers
    finish a collective preemption save before dying)."""

    def _spawn(self, tmp_path, body, monkeypatch):
        import subprocess as sp
        import time

        script = tmp_path / "t.py"
        script.write_text(textwrap.dedent(body))
        ready = tmp_path / "ready"
        env = dict(os.environ, READY=str(ready))
        p = sp.Popen([sys.executable, str(script)], env=env)
        deadline = time.time() + 30
        while not ready.exists():
            assert time.time() < deadline and p.poll() is None
            time.sleep(0.02)
        return p

    def test_grace_escalates_to_sigkill(self, tmp_path, monkeypatch):
        import time

        from tpudist.launch.run import _terminate

        p = self._spawn(tmp_path, """
            import os, signal, time
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
            open(os.environ["READY"], "w").close()
            time.sleep(120)
        """, monkeypatch)
        t0 = time.time()
        _terminate([p], grace_s=0.7)
        dt = time.time() - t0
        assert p.poll() == -9, "SIGTERM-ignoring worker must be SIGKILLed"
        assert dt >= 0.5, "killed before the grace window elapsed"
        assert dt < 30

    def test_graceful_exit_skips_kill(self, tmp_path, monkeypatch):
        import time

        from tpudist.launch.run import _terminate

        p = self._spawn(tmp_path, """
            import os, signal, sys, time
            signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
            open(os.environ["READY"], "w").close()
            time.sleep(120)
        """, monkeypatch)
        t0 = time.time()
        _terminate([p], grace_s=30.0)
        dt = time.time() - t0
        assert p.poll() == 0, "graceful worker must keep its clean exit"
        assert dt < 20, "waited out the grace window despite a clean exit"


def test_sigterm_during_backoff_skips_restart(tmp_path, monkeypatch, capsys):
    """SIGTERM landing BETWEEN attempts (during the restart backoff) must
    not launch a fresh group onto a node being reclaimed — the fresh group
    would never receive the group signal and would train until SLURM's
    SIGKILL."""
    import time as _time

    import tpudist.launch.run as run_mod

    _clean_env(monkeypatch)
    worker = _write_worker(tmp_path, """
        import os, pathlib
        pathlib.Path(os.environ["OUT_DIR"],
                     "a" + os.environ["TPUDIST_RESTART_COUNT"]).touch()
        raise SystemExit(3)
    """)
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    monkeypatch.setenv("OUT_DIR", str(out_dir))

    real_sleep = _time.sleep

    def sleep_with_sigterm(s):
        # The backoff sleep (>= 1s here) is where the "signal" lands; the
        # agent's 0.2s poll sleeps pass through (shortened to keep it fast).
        if s >= 1.0:
            run_mod._preempt_state["flag"] = True
        real_sleep(min(s, 0.05))

    monkeypatch.setattr(run_mod.time, "sleep", sleep_with_sigterm)
    rc = tpurun_main(["--nprocs", "1", "--max-restarts", "2",
                      "--restart-backoff", "1.5",
                      "--tmpdir", str(tmp_path / "s"),
                      "--", sys.executable, str(worker)])
    assert rc == 1
    assert sorted(p.name for p in out_dir.iterdir()) == ["a0"], (
        "a worker group was launched after the preemption signal")
    assert ("preemption signal during restart window"
            in capsys.readouterr().err)


def test_crash_record_written_atomically(tmp_path, monkeypatch):
    """Satellite: record writes go tmp + os.replace — a reader never sees
    a torn file, and failures to write never mask the original error."""
    import pytest as _pytest

    from tpudist.utils.record import record, write_error_record

    monkeypatch.setenv("TPUDIST_ERROR_FILE", str(tmp_path / "e_%r.json"))
    monkeypatch.setenv("TPUDIST_PROCESS_ID", "5")

    @record
    def boom():
        raise RuntimeError("kaboom")

    with _pytest.raises(RuntimeError, match="kaboom"):
        boom()
    rec = json.load(open(tmp_path / "e_5.json"))
    assert rec["exc_type"] == "RuntimeError" and rec["process_id"] == 5
    assert rec["pid"] == os.getpid()
    assert not list(tmp_path.glob("*.tmp*")), "tmp file leaked past replace"

    # unwritable destination: returns None, never raises
    monkeypatch.setenv("TPUDIST_ERROR_FILE",
                       str(tmp_path / "nodir" / "e_%r.json"))
    assert write_error_record({"exc_type": "X"}) is None


class TestStaging:
    def test_tarball_roundtrip(self, tmp_path):
        src = tmp_path / "dataset"
        (src / "sub").mkdir(parents=True)
        (src / "a.txt").write_text("hello")
        (src / "sub" / "b.txt").write_text("world")
        tb = create_tarball(src, tmp_path / "staged")
        assert tb.exists()
        # Second call: skip (job_submitter.sh:166-174 "tar once" semantics).
        mtime = tb.stat().st_mtime_ns
        assert create_tarball(src, tmp_path / "staged").stat().st_mtime_ns == mtime
        dest = tmp_path / "scratch"
        roots = extract_tarballs([tb], dest)
        assert (dest / "dataset" / "a.txt").read_text() == "hello"
        assert (dest / "dataset" / "sub" / "b.txt").read_text() == "world"
        assert roots == [dest / "dataset"]

    def test_missing_tarball_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            extract_tarballs([tmp_path / "nope.tar"], tmp_path)


SPEC = {
    "program": "examples/demo.py",
    "method": "grid",
    "metric": {"name": "loss/loss_X", "goal": "minimize"},
    "parameters": {
        "lr": {"values": [0.01, 0.001]},
        "batch_size": {"values": [128, 256, 512]},
        "seed": {"value": 0},
    },
    "command": ["python", "${program}", "--dry_run", "${args}"],
}


class TestSweep:
    def test_count_is_grid_product(self):
        # count_sweeps.bash parity: 2 * 3 * 1.
        assert SweepSpec.from_dict(SPEC).count() == 6

    def test_grid_enumeration_deterministic_and_complete(self):
        spec = SweepSpec.from_dict(SPEC)
        configs = [spec.config_at(i) for i in range(spec.count())]
        assert len({tuple(sorted(c.items())) for c in configs}) == 6
        assert configs[0] == {"lr": 0.01, "batch_size": 128, "seed": 0}
        assert spec.config_at(3) == configs[3]  # stable
        with pytest.raises(IndexError):
            spec.config_at(6)

    def test_command_interpolation(self):
        spec = SweepSpec.from_dict(SPEC)
        cmd = spec.command_for({"lr": 0.01, "batch_size": 128, "seed": 0})
        assert cmd[0] == sys.executable
        assert cmd[1] == "examples/demo.py"
        assert "--dry_run" in cmd
        assert "--lr=0.01" in cmd and "--batch_size=128" in cmd

    def test_yaml_cli_count(self, tmp_path):
        import yaml
        spec_path = tmp_path / "sweep.yml"
        spec_path.write_text(yaml.safe_dump(SPEC))
        out = subprocess.run(
            [sys.executable, "-m", "tpudist.launch.sweep", "count", str(spec_path)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        assert out.stdout.strip() == "6"

    def test_repo_sweeper_yml_parses(self):
        spec = SweepSpec.from_yaml(REPO / "launch" / "sweeper.yml")
        assert spec.count() == 12
        cfg = spec.config_at(0)
        assert set(cfg) == {"lr", "batch_size", "seed"}

    def test_agent_delegates_to_wandb_on_server_sweep(self, tmp_path,
                                                      monkeypatch):
        """WANDB_SWEEP_ID in the env (how job_submitter -j sweep -I ships
        the server sweep) makes the agent exec `wandb agent --count 1 <id>`
        instead of the local grid (sweep_cmd.txt:1 parity)."""
        import yaml

        import tpudist.launch.sweep as sweep_mod

        spec_path = tmp_path / "sweep.yml"
        spec_path.write_text(yaml.safe_dump(SPEC))
        calls = []
        monkeypatch.setattr(sweep_mod.subprocess, "call",
                            lambda cmd, **kw: calls.append(cmd) or 0)
        monkeypatch.setenv("WANDB_SWEEP_ID", "ent/proj/ab12cd")
        rc = sweep_mod.main(["agent", str(spec_path)])
        assert rc == 0
        assert len(calls) == 1
        assert calls[0][-4:] == ["agent", "--count", "1", "ent/proj/ab12cd"]

        # an explicit --index pins the run to the local grid even with the
        # ambient env var (a leftover WANDB_SWEEP_ID must not hijack it)
        calls.clear()
        rc = sweep_mod.main(["agent", str(spec_path), "--index", "2"])
        assert rc == 0
        assert len(calls) == 1
        assert "--dry_run" in calls[0]  # rendered local command template

        # without the env (and no flag): local grid agent runs the command
        calls.clear()
        monkeypatch.delenv("WANDB_SWEEP_ID")
        rc = sweep_mod.main(["agent", str(spec_path), "--index", "2"])
        assert rc == 0
        assert len(calls) == 1
        assert "--dry_run" in calls[0]


class TestBayesSweep:
    """Local bayes (TPE-style categorical sampler) — reference parity for
    sweeper.yml's `method` field without the W&B server round-trip."""

    SPEC = {
        "program": "obj.py",
        "method": "bayes",
        "metric": {"name": "loss", "goal": "minimize"},
        "parameters": {"lr": {"values": [0.001, 0.01, 0.1, 1.0]},
                       "wd": {"values": [0.0, 0.1]}},
    }

    def test_seed_phase_is_random_then_concentrates(self):
        spec = SweepSpec.from_dict(self.SPEC)
        # Before 4 observations: seeded random draws from the grid.
        c0 = spec.propose(0, [])
        assert c0["lr"] in self.SPEC["parameters"]["lr"]["values"]
        assert spec.propose(0, []) == c0  # deterministic per index

        # Feed observations where lr=0.01 is always in the best quartile.
        results = []
        for i, lr in enumerate([0.001, 0.01, 0.1, 1.0] * 4):
            results.append({"config": {"lr": lr, "wd": 0.0},
                            "metric": 0.1 if lr == 0.01 else 1.0 + i})
        picks = [spec.propose(i, results)["lr"] for i in range(40)]
        # The winning value must dominate proposals (smoothed sampling
        # keeps the others alive, so ~60% of draws, not 100%).
        counts = {v: picks.count(v) for v in (0.001, 0.01, 0.1, 1.0)}
        assert counts[0.01] >= 18, counts
        assert counts[0.01] > 2 * max(c for v, c in counts.items()
                                      if v != 0.01), counts

    def test_maximize_goal_flips_ranking(self):
        spec = SweepSpec.from_dict(dict(
            self.SPEC, metric={"name": "acc", "goal": "maximize"}))
        results = []
        for i, lr in enumerate([0.001, 0.01, 0.1, 1.0] * 4):
            results.append({"config": {"lr": lr, "wd": 0.0},
                            "metric": 0.9 if lr == 0.1 else 0.1})
        picks = [spec.propose(i, results)["lr"] for i in range(40)]
        counts = {v: picks.count(v) for v in (0.001, 0.01, 0.1, 1.0)}
        assert counts[0.1] >= 18, counts
        assert counts[0.1] > 2 * max(c for v, c in counts.items()
                                     if v != 0.1), counts

    def test_run_bayes_end_to_end_minimizes(self, tmp_path):
        """Full loop against a real subprocess objective: (log10(lr)+2)^2
        — optimum lr=0.01.  After 16 agent steps the results file must
        show proposals concentrating on the optimum."""
        import json

        obj = tmp_path / "obj.py"
        obj.write_text(
            "import math, sys\n"
            "from tpudist.launch.sweep import report_metric\n"
            "lr = float(next(a.split('=')[1] for a in sys.argv\n"
            "                if a.startswith('--lr=')))\n"
            "report_metric((math.log10(lr) + 2) ** 2)\n")
        spec = SweepSpec.from_dict(dict(
            self.SPEC,
            program=str(obj),
            command=["python", "${program}", "${args}"],
        ))
        results_path = tmp_path / "results.jsonl"
        env = {"PYTHONPATH": str(REPO)}  # the obj subprocess imports tpudist
        for i in range(16):
            rc = spec.run_bayes(i, results_path, extra_env=env)
            assert rc == 0
        rows = [json.loads(l) for l in results_path.read_text().splitlines()]
        assert len(rows) == 16
        assert all(r["metric"] is not None for r in rows)
        # The optimum keeps being revisited after the seed phase (strong
        # concentration at this sample size is asserted by the propose()
        # unit tests above; here we prove the full agent loop works).
        late_picks = [r["config"]["lr"] for r in rows[8:]]
        assert late_picks.count(0.01) >= 2, late_picks
        best = min(rows, key=lambda r: r["metric"])
        assert best["config"]["lr"] == 0.01

    def test_crashed_run_recorded_as_none(self, tmp_path):
        import json

        obj = tmp_path / "crash.py"
        obj.write_text("raise SystemExit(3)\n")
        spec = SweepSpec.from_dict(dict(
            self.SPEC, program=str(obj),
            command=["python", "${program}", "${args}"]))
        results_path = tmp_path / "r.jsonl"
        rc = spec.run_bayes(0, results_path)
        assert rc == 3
        row = json.loads(results_path.read_text())
        assert row["metric"] is None and row["rc"] == 3


class TestContinuousParameters:
    """min/max distribution parameters (W&B schema parity — r3 verdict:
    the local bayes covered only declared value grids)."""

    def _spec(self, method="random", **params):
        return SweepSpec.from_dict({
            "program": "obj.py", "method": method,
            "metric": {"name": "loss", "goal": "minimize"},
            "parameters": params,
        })

    def test_parse_distributions(self):
        spec = self._spec(
            lr={"min": 1e-4, "max": 1e-1, "distribution": "log_uniform"},
            layers={"min": 2, "max": 8},
            frac={"min": 0.0, "max": 1.0},
            step={"min": 0.0, "max": 2.0, "distribution": "q_uniform",
                  "q": 0.25},
        )
        draws = [spec.config_at(i) for i in range(64)]
        for c in draws:
            assert 1e-4 <= c["lr"] <= 1e-1
            assert isinstance(c["layers"], int) and 2 <= c["layers"] <= 8
            assert 0.0 <= c["frac"] <= 1.0
            assert abs(c["step"] / 0.25 - round(c["step"] / 0.25)) < 1e-9
        # int default for int bounds, uniform for float bounds
        assert any(c["layers"] != draws[0]["layers"] for c in draws)
        # log_uniform actually spreads over decades (a uniform draw over
        # [1e-4, 1e-1] would put ~99% of mass above 1e-3)
        frac_small = sum(c["lr"] < 1e-3 for c in draws) / len(draws)
        assert frac_small > 0.15, frac_small
        # deterministic per index
        assert spec.config_at(7) == spec.config_at(7)

    def test_invalid_specs_raise(self):
        import pytest

        with pytest.raises(ValueError, match="distribution"):
            self._spec(x={"min": 0, "max": 1, "distribution": "normal"})
        with pytest.raises(ValueError, match="min > 0"):
            self._spec(x={"min": 0.0, "max": 1.0,
                          "distribution": "log_uniform"})
        with pytest.raises(ValueError, match="needs q"):
            self._spec(x={"min": 0.0, "max": 1.0,
                          "distribution": "q_uniform"})

    def test_grid_and_count_reject_continuous(self):
        import pytest

        spec = self._spec(method="grid", lr={"min": 0.0, "max": 1.0})
        with pytest.raises(ValueError, match="continuous"):
            spec.count()
        with pytest.raises(ValueError, match="continuous"):
            spec.config_at(0)

    def test_bayes_concentrates_on_continuous_optimum(self):
        """TPE over a log_uniform lr: feed observations with the optimum
        at 1e-2; late proposals must sit closer to it (in log space) than
        prior draws."""
        import math

        spec = self._spec(
            method="bayes",
            lr={"min": 1e-4, "max": 1e-1, "distribution": "log_uniform"})
        rng_lrs = [spec.propose(i, [])["lr"] for i in range(48)]
        results = [
            {"config": {"lr": lr}, "metric": (math.log10(lr) + 2) ** 2}
            for lr in rng_lrs
        ]
        props = [spec.propose(100 + i, results)["lr"] for i in range(48)]

        def mean_dist(vals):
            return sum(abs(math.log10(v) + 2) for v in vals) / len(vals)

        assert mean_dist(props) < 0.6 * mean_dist(rng_lrs), (
            mean_dist(props), mean_dist(rng_lrs))

    def test_q_uniform_respects_offgrid_bounds(self):
        """q-rounding of a clamped draw must never step outside [min,max]
        when the bounds aren't multiples of q (review finding)."""
        from tpudist.launch.sweep import Continuous

        p = Continuous(lo=0.2, hi=1.0, distribution="q_uniform", q=0.5)
        import random as _r

        vals = {p.sample(_r.Random(i)) for i in range(200)}
        assert vals <= {0.5, 1.0}, vals  # in-range multiples only
        assert p.from_t(0.2) == 0.5  # 0.2 rounds down to 0.0 -> re-clamped

    def test_int_uniform_endpoints_get_full_mass(self):
        """Uniform over the integers, not uniform-then-round (which halves
        endpoint probability — review finding)."""
        from tpudist.launch.sweep import Continuous

        p = Continuous(lo=2, hi=4, distribution="int_uniform")
        import random as _r

        draws = [p.sample(_r.Random(i)) for i in range(900)]
        counts = {v: draws.count(v) for v in (2, 3, 4)}
        assert all(c > 230 for c in counts.values()), counts

    def test_run_index_with_continuous_random(self, tmp_path):
        """The agent CLI path must not call count() on continuous specs
        (review finding: the progress print crashed method random)."""
        import sys as _sys

        obj = tmp_path / "ok.py"
        obj.write_text("print('ran')\n")
        spec = self._spec(lr={"min": 1e-4, "max": 1e-1,
                              "distribution": "log_uniform"})
        spec = dataclasses.replace(
            spec, program=str(obj),
            command=[_sys.executable, "${program}", "${args}"])
        assert spec.run_index(0) == 0

    def test_continuous_composes_with_grid_dims(self):
        """Mixed spec: categorical TPE + continuous TPE in one proposal."""
        spec = self._spec(
            method="bayes",
            lr={"min": 1e-4, "max": 1e-1, "distribution": "log_uniform"},
            wd={"values": [0.0, 0.1]},
        )
        results = [{"config": {"lr": 10 ** -(2 + 0.01 * i), "wd": 0.1},
                    "metric": float(i)} for i in range(12)]
        c = spec.propose(5, results)
        assert 1e-4 <= c["lr"] <= 1e-1 and c["wd"] in (0.0, 0.1)


def test_locked_append_under_concurrency(tmp_path):
    """Concurrent agents share the bayes results file: every appended
    line must land whole (O_APPEND + flock)."""
    import json
    import threading

    from tpudist.launch.sweep import _locked_append

    path = tmp_path / "results.jsonl"
    n_threads, n_each = 8, 50

    def writer(t):
        for i in range(n_each):
            _locked_append(path, json.dumps(
                {"t": t, "i": i, "pad": "x" * 200}) + "\n")

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lines = path.read_text().splitlines()
    assert len(lines) == n_threads * n_each
    seen = {(json.loads(l)["t"], json.loads(l)["i"]) for l in lines}
    assert len(seen) == n_threads * n_each
