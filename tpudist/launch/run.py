"""``tpurun`` — per-node process agent (torchrun equivalent).

Replaces the reference's ``torchrun_launcher.sh`` + the torchrun binary
itself (SURVEY.md §2.2 B2, §3.1):

- rendezvous: ``--coordinator host:port`` is the c10d ``--rdzv_endpoint``
  analog; ``--standalone`` (implied when ``--nnodes 1``) picks a free
  localhost port like torchrun's ``--standalone``
  (``torchrun_launcher.sh:13-14``).
- env contract: workers receive ``TPUDIST_COORDINATOR`` /
  ``TPUDIST_NUM_PROCESSES`` / ``TPUDIST_PROCESS_ID`` /
  ``TPUDIST_LOCAL_RANK`` / ``TPUDIST_LOCAL_WORLD_SIZE`` (consumed by
  ``tpudist.runtime.bootstrap.resolve_process_context`` priority 2).
- elasticity: ``--max-restarts`` (default 3 like
  ``torchrun_launcher.sh:19``) relaunches the *whole local worker group*
  with exponential backoff when any worker fails.  JAX's coordination
  service is not per-process elastic, so this is whole-group semantics
  (SURVEY.md §5.3); on multi-node jobs the peer agents' workers die on
  coordinator loss and their agents restart them too, converging on a
  fresh rendezvous for the same ``--run-id``.
- crash records: workers decorated with ``tpudist.utils.record.record``
  write structured tracebacks to ``TPUDIST_ERROR_FILE``; the agent
  collects and surfaces the *first* failure (the ``@record`` +
  elastic-error-file pattern, ``demo.py:14,156``).
- preemption: SLURM delivers SIGTERM to the agent's PROCESS GROUP ahead
  of a requeue.  The agent must not die under the workers mid-save: its
  handler forwards SIGTERM to any worker that did not share the group
  signal, then the agent WAITS for the group to finish its collective
  preemption checkpoint (``tpudist.runtime.preemption`` in the workers),
  skips the restart loop (the machine is going away), surfaces the
  outcome, and exits with the group's status.
- data staging: ``--stage-data a.tar.gz,b.tar.gz`` extracts into the
  job-local tmpdir before workers start (``torchrun_launcher.sh:35-40``).
- command validation: like ``torchrun_launcher.sh:23-25`` the worker
  command must start with ``python`` (or be a ``-m`` module invocation).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

from tpudist.runtime.bootstrap import find_free_port
from tpudist.runtime.watchdog import WATCHDOG_EXIT_CODE


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpurun",
        description="tpudist per-node process agent (torchrun equivalent)",
    )
    p.add_argument("--nprocs", "--nproc-per-node", dest="nprocs", type=int, default=1,
                   help="worker processes on this node (torchrun --nproc_per_node)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--coordinator", "--rdzv-endpoint", dest="coordinator", default=None,
                   help="host:port of process 0's coordination service")
    p.add_argument("--standalone", action="store_true",
                   help="single-node: rendezvous on a free localhost port")
    p.add_argument("--run-id", default=None,
                   help="job-scoped rendezvous id (torchrun --rdzv_id=$SLURM_JOB_ID)")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="whole-group restarts on worker failure "
                        "(torchrun_launcher.sh:19 default)")
    p.add_argument("--elastic", action="store_true",
                   help="on restart exhaustion, relaunch the group at the "
                        "SURVIVING world size instead of giving up: crash "
                        "records identify the dead ranks, the remaining "
                        "workers renumber 0..n'-1 with a fresh "
                        "TPUDIST_NUM_PROCESSES, and the restart budget "
                        "resets per world size (single-node agents only "
                        "for now — the rank renumbering is node-local)")
    p.add_argument("--restart-backoff", type=float, default=5.0,
                   help="base seconds between restarts (doubles each retry)")
    p.add_argument("--stage-data", default=None,
                   help="comma-separated tarballs extracted into the job tmpdir "
                        "before workers start")
    p.add_argument("--tmpdir", default=None,
                   help="job-local scratch (default: $TPUDIST_TMPDIR or a fresh "
                        "tempdir); exported to workers as TPUDIST_TMPDIR")
    p.add_argument("--error-dir", default=None,
                   help="directory for per-rank crash records (default: tmpdir)")
    p.add_argument("--telemetry-dir", default=None,
                   help="where workers stream per-rank telemetry JSONL and "
                        "the end-of-run goodput report lands (default: "
                        "$TPUDIST_TELEMETRY_DIR or <tmpdir>/telemetry; "
                        "TPUDIST_TELEMETRY=0 disables)")
    p.add_argument("--devices-per-proc", type=int, default=None,
                   help="emulated devices per worker (sets XLA's "
                        "host-platform device-count flag in the worker "
                        "env) — lets CPU smoke rungs and tests run "
                        "per-process multi-device meshes, e.g. a sharded "
                        "serve worker per process")
    p.add_argument("--no-python-check", action="store_true",
                   help="allow worker commands that do not start with 'python'")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="worker command: python script.py [args...]")
    return p


def _validate_cmd(cmd: List[str], allow_any: bool) -> List[str]:
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        raise SystemExit("tpurun: no worker command given")
    if not allow_any and not os.path.basename(cmd[0]).startswith("python"):
        # torchrun_launcher.sh:23-25 — "the job command must start with python".
        raise SystemExit(
            f"tpurun: worker command must start with 'python' (got {cmd[0]!r}); "
            "pass --no-python-check to override"
        )
    return cmd


def _worker_env(base: Dict[str, str], *, coordinator: Optional[str], world: int,
                rank: int, local_rank: int, nprocs: int, run_id: str,
                restart_count: int, error_template: str, tmpdir: str,
                telemetry_dir: Optional[str] = None,
                devices_per_proc: Optional[int] = None) -> Dict[str, str]:
    env = dict(base)
    if devices_per_proc and devices_per_proc > 0:
        # Per-process emulated multi-device mesh (CPU rigs): the XLA
        # host-platform flag must be in the env BEFORE jax initializes
        # its backends in the worker.  An existing device-count flag in
        # the inherited XLA_FLAGS is replaced, not duplicated (last
        # occurrence wins in XLA, but a stale first one is confusing in
        # ps output and logs).
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={devices_per_proc}")
        env["XLA_FLAGS"] = " ".join(flags)
    env.update({
        "TPUDIST_NUM_PROCESSES": str(world),
        "TPUDIST_PROCESS_ID": str(rank),
        "TPUDIST_LOCAL_RANK": str(local_rank),
        "TPUDIST_LOCAL_WORLD_SIZE": str(nprocs),
        "TPUDIST_RUN_ID": run_id,
        "TPUDIST_RESTART_COUNT": str(restart_count),
        "TPUDIST_ERROR_FILE": error_template,
        "TPUDIST_TMPDIR": tmpdir,
    })
    if coordinator:
        env["TPUDIST_COORDINATOR"] = coordinator
    if telemetry_dir:
        # All generations of all local workers stream into ONE dir — the
        # per-rank/per-generation file names keep them apart, and the
        # end-of-run merge joins them into the goodput report.
        env["TPUDIST_TELEMETRY_DIR"] = telemetry_dir
    # Scrape-endpoint port fan-out: the AGENT binds the configured port
    # before any worker launches, so workers inheriting the same value
    # would all fail to bind and silently lose their endpoints — exactly
    # the serve/train /metrics the feature exists for.  A fixed port P
    # maps workers to P+1+local_rank (deterministic, documented); 0
    # (ephemeral) passes through — every process binds its own.
    port = env.get("TPUDIST_METRICS_PORT", "").strip()
    if port and port.isdigit() and int(port) > 0:
        env["TPUDIST_METRICS_PORT"] = str(int(port) + 1 + local_rank)
    return env


def _read_crash_records(error_template: str, world: int) -> List[dict]:
    records = []
    for path in sorted(glob.glob(error_template.replace("%r", "*"))):
        try:
            with open(path) as f:
                records.append(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
    records.sort(key=lambda r: r.get("timestamp", 0))
    return records


# Signal-handler state: the live worker group and whether a preemption
# signal arrived.  Module-level (not closure) so the handler, the attempt
# loop, and tests all see one source of truth.
_preempt_state: dict = {"flag": False, "procs": []}

#: Ranks the LAST attempt observed failing spontaneously (nonzero exit
#: before the agent terminated the rest of the group).  A SIGKILLed
#: worker writes no crash record — this observation is what lets the
#: elastic path name the dead ranks anyway.
_last_failed_ranks: List[int] = []


def _handle_agent_sigterm(signum, frame):  # noqa: ARG001
    """Agent-side preemption: mark, forward to workers, keep running.

    Returning (instead of dying, the default SIGTERM action) is the whole
    point — the agent must stay alive to reap the workers' collective
    checkpoint save and report it."""
    _preempt_state["flag"] = True
    for p in list(_preempt_state["procs"]):
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass


def _terminate(procs: List[subprocess.Popen], grace_s: float = 10.0) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.time() + grace_s
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _require_chip_binding(nprocs: int, env) -> None:
    """One process for each chip: refuse ``--nprocs N>1`` on a TPU host
    unless the caller bound chips per worker.

    A chip belongs to one process at a time, and a JAX process claims
    every chip it can see — N unbound workers would all claim all of
    them, and all but one fail or hang.  The standard shape is ONE
    process per host that sees all local chips (what
    distributed_dispatcher/tpu_pod_run launch).  Bound means a
    ``TPU_*VISIBLE*`` variable in the environment (the caller's wrapper
    sets it per worker), or workers pinned off the TPU by
    ``JAX_PLATFORMS`` (CPU multi-process runs on a TPU host).
    """
    on_tpu_host = os.path.exists("/dev/accel0") or env.get("TPU_NAME")
    if nprocs <= 1 or not on_tpu_host:
        return
    bound = any(k.startswith("TPU_") and "VISIBLE" in k for k in env)
    platforms = env.get("JAX_PLATFORMS", "").lower()
    if bound or (platforms and "tpu" not in platforms.split(",")):
        return
    raise SystemExit(
        f"tpurun: --nprocs {nprocs} on a TPU host without per-worker chip "
        "binding: every worker would claim every chip, and a chip belongs "
        "to one process. Run 1 process per host (it sees all local chips), "
        "bind chips per worker (TPU_VISIBLE_* in the environment), or pin "
        "the workers to CPU with JAX_PLATFORMS=cpu — see launch/README.md")


def _run_attempt(cmd: List[str], args, coordinator: str, world: int,
                 run_id: str, restart_count: int, error_template: str,
                 tmpdir: str, telemetry_dir: Optional[str] = None,
                 nprocs: Optional[int] = None) -> int:
    """Launch the local worker group once; return 0 iff all workers exit 0.

    ``nprocs`` overrides ``args.nprocs`` — the elastic path relaunches
    with fewer local workers than the original request."""
    if nprocs is None:
        nprocs = args.nprocs
    procs: List[subprocess.Popen] = []
    _preempt_state["procs"] = procs
    base_env = dict(os.environ)
    for i in range(nprocs):
        rank = args.node_rank * nprocs + i
        env = _worker_env(base_env, coordinator=coordinator, world=world,
                          rank=rank, local_rank=i, nprocs=nprocs,
                          run_id=run_id, restart_count=restart_count,
                          error_template=error_template, tmpdir=tmpdir,
                          telemetry_dir=telemetry_dir,
                          devices_per_proc=args.devices_per_proc)
        procs.append(subprocess.Popen(cmd, env=env))
    failed_rc = 0
    del _last_failed_ranks[:]
    try:
        live = list(procs)
        while live:
            for p in list(live):
                rc = p.poll()
                if rc is None:
                    continue
                live.remove(p)
                if rc != 0:
                    failed_rc = rc
                    # the rank that died on its own — a SIGKILLed worker
                    # leaves no crash record, so the agent's observation
                    # is the elastic path's dead-rank source of truth
                    _last_failed_ranks.append(
                        args.node_rank * nprocs + procs.index(p))
                    if _preempt_state["flag"]:
                        # Preempting: a straggler may still be finishing
                        # the collective save — keep waiting, don't kill.
                        continue
                    # One worker down ⇒ the group is done (the coordination
                    # service cannot re-admit a lone restarted process).
                    _terminate(live)
                    live = []
                    break
            time.sleep(0.2)
    except KeyboardInterrupt:
        _terminate(procs)
        raise
    finally:
        _preempt_state["procs"] = []
    return failed_rc


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    cmd = _validate_cmd(args.cmd, args.no_python_check)
    if args.nprocs < 1 or args.nnodes < 1 or not 0 <= args.node_rank < args.nnodes:
        raise SystemExit(
            f"tpurun: invalid topology nprocs={args.nprocs} nnodes={args.nnodes} "
            f"node_rank={args.node_rank}")

    if args.elastic and args.nnodes != 1:
        raise SystemExit(
            "tpurun: --elastic currently requires --nnodes 1 (survivor "
            "renumbering is node-local; multi-node elasticity needs a "
            "cross-agent rendezvous)")

    _require_chip_binding(args.nprocs, os.environ)
    world = args.nnodes * args.nprocs
    standalone = args.standalone or (args.nnodes == 1 and args.coordinator is None)
    if standalone:
        coordinator = f"127.0.0.1:{find_free_port()}" if world > 1 else ""
    else:
        if not args.coordinator:
            raise SystemExit("tpurun: --coordinator required for multi-node jobs "
                             "(or pass --standalone)")
        coordinator = args.coordinator

    from tpudist.launch.staging import job_tmpdir

    run_id = args.run_id or os.environ.get("SLURM_JOB_ID") or f"tpurun-{os.getpid()}"
    tmpdir = args.tmpdir or job_tmpdir()
    owns_tmpdir = tmpdir is None
    if owns_tmpdir:
        tmpdir = tempfile.mkdtemp(prefix=f"tpudist_{run_id}_")
        # Job-lifetime scratch: remove on agent exit only when we created it
        # (a scheduler-provided dir is the scheduler's to clean).
        import atexit
        import shutil
        atexit.register(shutil.rmtree, tmpdir, ignore_errors=True)
    tmpdir = str(tmpdir)
    os.makedirs(tmpdir, exist_ok=True)
    error_dir = args.error_dir or tmpdir
    os.makedirs(error_dir, exist_ok=True)

    # Telemetry: workers stream per-rank/per-generation span JSONL into one
    # dir; the agent merges it into report.json/report.md on exit — every
    # run (clean, crashed, preempted, restart-exhausted) ends with a
    # goodput report next to the crash records.
    from tpudist.telemetry import enabled_from_env as _telemetry_enabled

    telemetry_dir: Optional[str] = None
    if _telemetry_enabled():
        # Default placement must survive the agent: an agent-owned tmpdir
        # is rmtree'd at exit, which would delete the very report a
        # crashed run exists to leave behind — fall back to the bare-run
        # default (runs/telemetry, cwd) in that case.
        telemetry_dir = (args.telemetry_dir
                         or os.environ.get("TPUDIST_TELEMETRY_DIR")
                         or (os.path.join("runs", "telemetry") if owns_tmpdir
                             else os.path.join(tmpdir, "telemetry")))

    # The agent has no global telemetry session; staging phases and the
    # restart_exhausted / world_resized lifecycle events record into ONE
    # lazily-created agent stream (pseudo-rank = initial world +
    # node_rank: past every worker rank AND distinct per node, so agents
    # sharing a --telemetry-dir never clobber each other's stream).
    # Event-only, so the aggregator never counts it as a goodput rank.
    agent_tele: Dict[str, object] = {"session": None}
    agent_rank = world + args.node_rank

    def _agent_session():
        if not telemetry_dir or agent_tele["session"] is not None:
            return agent_tele["session"]
        try:
            from tpudist import telemetry as _tele

            agent_tele["session"] = _tele.TelemetrySession(
                telemetry_dir, rank=agent_rank, generation=0)
        except Exception:  # noqa: BLE001 — telemetry never kills the run
            pass
        return agent_tele["session"]

    # Live observability: the agent exposes /metrics /healthz /statusz
    # when TPUDIST_METRICS_PORT is set — fleet-level restart/resize state
    # that was stderr-only before.  Best-effort; never kills the run.
    agent_state = {"world": world, "generation": 0, "attempt_in_world": 0,
                   "nprocs": args.nprocs, "run_id": run_id,
                   "restarts_max": args.max_restarts, "elastic":
                   bool(getattr(args, "elastic", False))}
    try:
        from tpudist.telemetry import statusz as _statusz

        _agent_statusz = _statusz.ensure_started()
        if _agent_statusz is not None:
            _agent_statusz.register_status(
                "tpurun", lambda: dict(agent_state))
    except Exception:  # noqa: BLE001
        pass

    if args.stage_data:
        from tpudist.launch.staging import extract_tarballs
        from tpudist.utils.profiling import StageTimer

        stage_timer = StageTimer()
        with stage_timer.phase("stage_data"):
            extract_tarballs(args.stage_data.split(","), tmpdir)
        s = _agent_session()
        if s is not None:
            stage_timer.emit(session=s)

    # Preemption protocol: SLURM SIGTERMs the agent's process group; the
    # agent must survive it (forwarding to workers that missed the group
    # signal), wait out the workers' collective checkpoint save, and NOT
    # restart — the allocation is going away.  Handler installed only in
    # the main thread (CPython restriction); restored on exit so embedding
    # callers (tests) keep their own handlers.
    _preempt_state["flag"] = False
    prev_handler = None
    import threading

    in_main_thread = threading.current_thread() is threading.main_thread()
    if in_main_thread:
        prev_handler = signal.signal(signal.SIGTERM, _handle_agent_sigterm)
    try:
        max_attempts = args.max_restarts + 1
        nprocs = args.nprocs
        attempt_in_world = 0  # restarts consumed at the CURRENT world size
        generation = 0        # TPUDIST_RESTART_COUNT across ALL launches,
        #                       monotone through elastic resizes so every
        #                       telemetry stream / crash record is distinct
        while True:
            error_template = os.path.join(
                error_dir, f"error_attempt{generation}_rank%r.json")
            if generation > 0:
                backoff = args.restart_backoff * (
                    2 ** max(0, attempt_in_world - 1))
                print(f"[tpurun] restarting worker group (attempt "
                      f"{attempt_in_world + 1}/{max_attempts} at world "
                      f"{world}) in {backoff:.1f}s", file=sys.stderr)
                time.sleep(backoff)
                if standalone and world > 1:
                    # Fresh rendezvous port: the dead service may linger in
                    # TIME_WAIT.
                    coordinator = f"127.0.0.1:{find_free_port()}"
            if _preempt_state["flag"]:
                # SIGTERM landed between attempts (e.g. during backoff):
                # a fresh group would never have received the group
                # signal and would train until SLURM's SIGKILL — don't
                # launch onto a node being reclaimed.
                print("[tpurun] preemption signal during restart window; "
                      "not launching a new worker group", file=sys.stderr)
                return 1
            rc = _run_attempt(cmd, args, coordinator, world, run_id,
                              generation, error_template, tmpdir,
                              telemetry_dir=telemetry_dir, nprocs=nprocs)
            if rc == WATCHDOG_EXIT_CODE:
                # The hang watchdog aborted a wedged worker on purpose so
                # THIS restart loop could re-admit the group — say so (the
                # stall stack dump is in the crash record below).
                print("[tpurun] worker group aborted by the hang watchdog "
                      f"(exit {WATCHDOG_EXIT_CODE}): a stalled step or "
                      "wedged collective was detected", file=sys.stderr)
            if _preempt_state["flag"]:
                ok = rc == 0
                print("[tpurun] preemption: worker group "
                      f"{'saved and exited cleanly' if ok else f'exited rc={rc}'} "
                      "after SIGTERM; not restarting", file=sys.stderr)
                return 0 if ok else 1
            if rc == 0:
                return 0
            records = _read_crash_records(error_template, world)
            if records:
                first = records[0]
                print(f"[tpurun] first failure: rank {first.get('process_id')} "
                      f"{first.get('exc_type')}: {first.get('message')}",
                      file=sys.stderr)
                tb = first.get("traceback")
                if tb:
                    print(tb, file=sys.stderr)
            else:
                print(f"[tpurun] worker group failed (exit {rc}); no crash "
                      f"record written (segfault or unhandled signal?)",
                      file=sys.stderr)
            generation += 1
            attempt_in_world += 1
            agent_state.update(generation=generation,
                               attempt_in_world=attempt_in_world)
            if attempt_in_world < max_attempts:
                continue
            # Restart budget exhausted at this world size.  Stamp the
            # event into the merged report (exhaustion used to be
            # stderr-only — invisible to `tpudist.telemetry report`)...
            # Dead ranks = the CULPRITS only: the timestamp-first crash
            # record plus the agent's first observed spontaneous exit.
            # Victims of the cascade (ranks whose collectives error and
            # record before the agent's SIGTERM lands) must NOT count —
            # over-shrinking throws away healthy workers, while
            # under-shrinking is safe: a still-doomed smaller world just
            # exhausts again and shrinks again.
            dead = set(_last_failed_ranks)
            if records and isinstance(records[0].get("process_id"), int):
                dead.add(int(records[0]["process_id"]))
            dead = sorted(dead)
            first = records[0] if records else {}
            s = _agent_session()
            if s is not None:
                s.event("restart_exhausted", attempts=attempt_in_world,
                        world=world, dead_ranks=dead,
                        exc_type=first.get("exc_type"),
                        message=str(first.get("message", ""))[:200])
                s.flush()
            # ...then either give up (fixed-size semantics) or relaunch
            # the group at the SURVIVING world size (--elastic): the
            # crash records name the dead ranks, survivors renumber
            # 0..n'-1, and the workers rebuild their mesh from the new
            # TPUDIST_NUM_PROCESSES.  The trainer resumes through the
            # reshardable-checkpoint path.
            if args.elastic and world > 1:
                new_world = max(1, world - max(1, len(dead)))
                print(f"[tpurun] elastic: restart budget exhausted at "
                      f"world {world}; relaunching at surviving world "
                      f"{new_world} (dead ranks: {dead or 'unknown'})",
                      file=sys.stderr)
                if s is not None:
                    s.event("world_resized", from_world=world,
                            to_world=new_world, generation=generation,
                            dead_ranks=dead)
                    s.flush()
                world = nprocs = new_world
                attempt_in_world = 0
                agent_state.update(world=world, nprocs=nprocs,
                                   attempt_in_world=0)
                if standalone:
                    coordinator = (f"127.0.0.1:{find_free_port()}"
                                   if world > 1 else "")
                continue
            print(f"[tpurun] giving up after {attempt_in_world} attempts "
                  f"at world {world}", file=sys.stderr)
            return 1
    finally:
        session = agent_tele["session"]
        if session is not None:
            try:
                session.close()
            except Exception:  # noqa: BLE001
                pass
        if in_main_thread and prev_handler is not None:
            try:
                signal.signal(signal.SIGTERM, prev_handler)
            except (ValueError, OSError):
                pass
        # Every exit path — clean, crashed, preempted, restart-exhausted —
        # ends with the merged goodput report next to the crash records
        # (a crashed run is exactly the one whose wall-clock needs
        # attributing).  A run-training rank 0 may already have written
        # one at its own finalize; the agent's merge supersedes it with
        # the view joined across ALL generations.
        _emit_telemetry_report(telemetry_dir)


def _emit_telemetry_report(telemetry_dir: Optional[str]) -> None:
    """Merge the workers' telemetry into report.json/report.md and print
    the headline.  Best-effort by design: report failure must never mask
    the run's own exit status."""
    if not telemetry_dir:
        return
    try:
        from tpudist.telemetry.aggregate import write_reports

        report, paths = write_reports(telemetry_dir)
        if report.get("num_records", 0) == 0:
            return
        g = report["goodput"]
        print(
            f"[tpurun] goodput report ({paths['md'] or telemetry_dir}): "
            f"wall {report['wall_clock_s']:.1f}s over "
            f"{report['num_ranks']} rank(s) x "
            f"{report['generations']} generation(s) — "
            f"step {g['step']['frac'] * 100:.0f}%, "
            f"compile {g['compile']['frac'] * 100:.0f}%, "
            f"data {g['data']['frac'] * 100:.0f}%, "
            f"ckpt {g['ckpt']['frac'] * 100:.0f}%, "
            f"idle {g['idle']['frac'] * 100:.0f}%, "
            f"resize {g.get('resize', {}).get('frac', 0.0) * 100:.0f}%, "
            f"lost-to-restart {g['lost_restart']['frac'] * 100:.0f}%",
            file=sys.stderr,
        )
    except Exception as e:  # noqa: BLE001 — never mask the run's status
        print(f"[tpurun] telemetry report failed: {type(e).__name__}: {e}",
              file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
