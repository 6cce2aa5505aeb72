"""True multi-process integration: tpurun spawns worker processes that
rendezvous through ``jax.distributed`` over localhost, build a global mesh,
and run cross-process collectives — the TPU-analog of the reference's
multi-rank Gloo CPU runs (``salloc_torchrun.sh:94-95``, SURVEY.md §4.5:
the reference used Gloo for *real* multi-node CPU runs, never simulation;
this test keeps that realism on one host).

Workers run with ``JAX_CPU_COLLECTIVES_IMPLEMENTATION=gloo`` so device
collectives cross process boundaries on CPU.
"""

import json
import os
import sys
import textwrap
from pathlib import Path

import pytest

from tpudist.launch.run import main as tpurun_main

REPO = Path(__file__).resolve().parent.parent

WORKER = """
    import json, os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)  # 1 device per process
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudist.runtime import bootstrap
    from tpudist.runtime.mesh import data_parallel_mesh
    from tpudist.comm import collectives

    ctx = bootstrap.initialize()
    assert jax.process_count() == ctx.num_processes, (
        jax.process_count(), ctx.num_processes)
    assert jax.process_index() == ctx.process_id

    mesh = data_parallel_mesh()
    rank = ctx.process_id

    # 1. Host-fabric all-reduce (Gloo-group analog): sum of ranks.
    total = collectives.host_allreduce_sum(np.float64(rank))
    expect = sum(range(ctx.num_processes))
    assert float(total) == expect, (total, expect)

    # 2. Batch-weighted scalar mean (demo.py:113-121 semantics).
    mean = collectives.cross_process_mean_scalar(float(rank), weight=256.0)
    assert abs(mean - expect / ctx.num_processes) < 1e-9

    # 3. Device-fabric collective through a global sharded array: each
    #    process contributes its shard; a jitted global sum crosses the
    #    process boundary (the gradient-psum path).
    sharding = NamedSharding(mesh, P("data"))
    local = np.full((2, 4), float(rank), np.float32)
    garr = collectives.device_put_global(local, sharding)
    assert garr.shape == (2 * ctx.num_processes, 4)
    s = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))(garr)
    assert float(s) == 8.0 * expect, (float(s), 8.0 * expect)

    # 4. Barrier + teardown discipline (demo.py:177-178).
    collectives.barrier()
    out = os.path.join(os.environ["OUT_DIR"], f"ok{rank}.json")
    json.dump({"rank": rank, "world": ctx.num_processes,
               "source": ctx.launch_source}, open(out, "w"))
    bootstrap.shutdown()
"""


def _run_workers(tmp_path, monkeypatch, worker_src, nprocs):
    """Shared rig: write the worker, scrub launcher env, run via tpurun."""
    worker = tmp_path / "worker.py"
    worker.write_text(textwrap.dedent(worker_src))
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    for var in list(os.environ):
        if var.startswith(("TPUDIST_", "SLURM_", "OMPI_")) or var in (
                "RANK", "WORLD_SIZE", "MASTER_ADDR", "NODE_RANK"):
            monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("OUT_DIR", str(out_dir))
    monkeypatch.setenv("PYTHONPATH", str(REPO))
    rc = tpurun_main(["--nprocs", str(nprocs), "--max-restarts", "0",
                      "--tmpdir", str(tmp_path / "scratch"),
                      "--", sys.executable, str(worker)])
    return rc, out_dir


@pytest.mark.parametrize("nprocs", [2, 4])
def test_multiprocess_rendezvous_and_collectives(tmp_path, monkeypatch, nprocs):
    rc, out_dir = _run_workers(tmp_path, monkeypatch, WORKER, nprocs)
    assert rc == 0
    recs = [json.load(open(f)) for f in sorted(out_dir.glob("ok*.json"))]
    assert len(recs) == nprocs
    assert {r["rank"] for r in recs} == set(range(nprocs))
    assert all(r["source"] == "tpudist" for r in recs)


def test_torchrun_style_env_contract(tmp_path, monkeypatch):
    """The same worker must bootstrap from MASTER_ADDR/RANK/WORLD_SIZE env
    (the reference's torchrun contract, demo.py:25-34) with no tpurun."""
    import subprocess
    from tpudist.runtime.bootstrap import find_free_port

    worker = tmp_path / "worker.py"
    worker.write_text(textwrap.dedent(WORKER))
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    port = find_free_port()
    procs = []
    for rank in range(2):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("TPUDIST_", "SLURM_", "OMPI_"))}
        env.update({"MASTER_ADDR": "127.0.0.1", "MASTER_PORT": str(port),
                    "RANK": str(rank), "WORLD_SIZE": "2",
                    "LOCAL_RANK": str(rank), "LOCAL_WORLD_SIZE": "2",
                    "OUT_DIR": str(out_dir), "PYTHONPATH": str(REPO)})
        procs.append(subprocess.Popen([sys.executable, str(worker)], env=env))
    for p in procs:
        assert p.wait(timeout=240) == 0
    recs = [json.load(open(f)) for f in sorted(out_dir.glob("ok*.json"))]
    assert len(recs) == 2
    assert all(r["source"] == "torchrun" for r in recs)


HYBRID_WORKER = """
    import json, os

    os.environ["JAX_PLATFORMS"] = "cpu"
    # 2 virtual devices per process -> a 2-host x 2-chip "pod".
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"

    import jax
    from tpudist.runtime import bootstrap
    from tpudist.runtime.mesh import MeshConfig, make_hybrid_mesh

    ctx = bootstrap.initialize()
    mesh = make_hybrid_mesh(MeshConfig(data=-1, model=2))
    # data axis = 2 (one per host, over DCN); model axis = 2 (within host,
    # over ICI): each data row must be one process's devices.
    assert dict(zip(mesh.axis_names, mesh.devices.shape))["data"] == 2
    for row in mesh.devices.reshape(2, -1):
        procs = {d.process_index for d in row}
        assert len(procs) == 1, f"model axis crossed hosts: {procs}"
    out = os.path.join(os.environ["OUT_DIR"], f"hy{ctx.process_id}.json")
    json.dump({"rank": ctx.process_id}, open(out, "w"))
    bootstrap.shutdown()
"""


def test_hybrid_mesh_keeps_ici_axes_within_host(tmp_path, monkeypatch):
    """2 processes x 2 devices: the hybrid mesh must put the model axis
    inside each process (ICI) and the data axis across processes (DCN)."""
    rc, out_dir = _run_workers(tmp_path, monkeypatch, HYBRID_WORKER, 2)
    assert rc == 0
    assert len(list(out_dir.glob("hy*.json"))) == 2


RING_WORKER = """
    import json, os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)  # 1 device per process
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudist.runtime import bootstrap
    from tpudist.comm import collectives
    from tpudist.ops import attention_reference
    from tpudist.parallel import make_ring_attention
    from tpudist.runtime.mesh import AXIS_SEQ

    ctx = bootstrap.initialize()
    mesh = Mesh(np.asarray(jax.devices()), axis_names=(AXIS_SEQ,))

    # Same global q/k/v on every process (deterministic seed); the ring
    # shards seq across the two processes, ppermute hops cross the
    # process boundary through the gloo device fabric.
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 32, 16), jnp.float32)
               for kk in ks)
    spec = NamedSharding(mesh, P(None, None, AXIS_SEQ, None))
    sl = slice(ctx.process_id * 16, (ctx.process_id + 1) * 16)
    gq, gk, gv = (collectives.device_put_global(
        np.asarray(a)[:, :, sl], spec, global_shape=(1, 2, 32, 16))
        for a in (q, k, v))

    ring = make_ring_attention(mesh, causal=True, kernel="flash",
                               interpret=True)
    out = ring(gq, gk, gv)
    ref = attention_reference(q, k, v, causal=True)
    local = np.asarray(
        [s.data for s in out.addressable_shards][0])
    lref = np.asarray(ref)[:, :, ctx.process_id * 16:(ctx.process_id + 1) * 16]
    err = float(np.max(np.abs(local - lref)))
    assert err < 2e-5, err

    collectives.barrier()
    outp = os.path.join(os.environ["OUT_DIR"], f"ring{ctx.process_id}.json")
    json.dump({"rank": ctx.process_id, "err": err}, open(outp, "w"))
    bootstrap.shutdown()
"""


def test_flash_ring_crosses_process_boundary(tmp_path, monkeypatch):
    """The Pallas-per-hop ring runs over a 2-process seq mesh: each hop's
    K/V ppermute crosses the process boundary (gloo device fabric), each
    shard's output matches the dense reference — the kernels compose with
    jax.distributed, not just the single-process virtual mesh."""
    rc, out_dir = _run_workers(tmp_path, monkeypatch, RING_WORKER, 2)
    assert rc == 0
    recs = [json.load(open(f)) for f in sorted(out_dir.glob("ring*.json"))]
    assert len(recs) == 2


ELASTIC_WORKER = """
    import json, os, threading, time

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)  # 1 device per process
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from tpudist.runtime import bootstrap
    from tpudist.comm import collectives

    ctx = bootstrap.initialize()
    attempt = int(os.environ["TPUDIST_RESTART_COUNT"])
    rank = ctx.process_id

    # Every rank proves the (re-)rendezvous actually formed the full world
    # before anything else.
    total = collectives.host_allreduce_sum(np.float64(rank))
    assert float(total) == sum(range(ctx.num_processes))

    if attempt == 0:
        # Mid-run failure in group A: rank 0 dies hard (no cleanup — a
        # real crash).  The other group's workers discover it through
        # their next collective erroring (gloo peer gone) — the
        # NCCL_ASYNC_ERROR_HANDLING analog — with a watchdog bail as the
        # backstop, then exit nonzero so THEIR agent restarts them too.
        marker = os.path.join(os.environ["OUT_DIR"],
                              f"attempt0_rank{rank}.json")
        json.dump({"rank": rank, "world": ctx.num_processes}, open(marker, "w"))
        if rank == 0:
            os._exit(17)
        threading.Timer(60.0, lambda: os._exit(1)).start()
        try:
            for _ in range(100):
                collectives.host_allreduce_sum(np.float64(1.0))
                time.sleep(0.2)
            os._exit(1)  # rank 0's death must have been noticed by now
        except BaseException:
            os._exit(1)

    # Attempt 1: the restarted world trains to convergence.
    from tpudist.data import make_toy_data
    from tpudist.models import create_toy_model
    from tpudist.runtime.mesh import data_parallel_mesh
    from tpudist.train import init_model_states, make_scanned_train_step

    mesh = data_parallel_mesh()
    kx, = jax.random.split(jax.random.PRNGKey(0), 1)
    mx, px = create_toy_model(kx)
    models = {"m": (mx.apply, px)}
    tx = optax.adam(1e-2)
    states = init_model_states(models, tx)
    step = make_scanned_train_step({"m": mx.apply}, tx, mesh)
    data = make_toy_data(seed=0)
    rng = np.random.default_rng(rank)
    x_all, y_all = jnp.asarray(data.x), jnp.asarray(data.y)
    first = last = None
    for _ in range(6):
        idx = jnp.asarray(rng.integers(0, len(data), size=(32, 64)), jnp.int32)
        states, losses = step(states, x_all, y_all, idx)
        val = float(np.asarray(losses["m"]).ravel()[-1])
        if first is None:
            first = val
        last = val
    assert last < first, (first, last)

    collectives.barrier()
    out = os.path.join(os.environ["OUT_DIR"], f"elastic{rank}.json")
    json.dump({"rank": rank, "attempt": attempt, "run_id":
               os.environ["TPUDIST_RUN_ID"], "first": first, "last": last},
              open(out, "w"))
    bootstrap.shutdown()
"""


def test_multi_agent_elastic_restart(tmp_path, monkeypatch):
    """torchrun c10d semantics (torchrun_launcher.sh:16-19): two tpurun
    agents share one rendezvous (--coordinator + --run-id); a worker in
    agent A's group dies mid-run; BOTH agents must restart their groups,
    re-rendezvous into the same world, and train to convergence."""
    import concurrent.futures
    import textwrap as tw

    from tpudist.runtime.bootstrap import find_free_port

    worker = tmp_path / "worker.py"
    worker.write_text(tw.dedent(ELASTIC_WORKER))
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    for var in list(os.environ):
        if var.startswith(("TPUDIST_", "SLURM_", "OMPI_")) or var in (
                "RANK", "WORLD_SIZE", "MASTER_ADDR", "NODE_RANK"):
            monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("OUT_DIR", str(out_dir))
    monkeypatch.setenv("PYTHONPATH", str(REPO))
    coordinator = f"127.0.0.1:{find_free_port()}"

    def agent(node_rank):
        return tpurun_main([
            "--nprocs", "1", "--nnodes", "2", "--node-rank", str(node_rank),
            "--coordinator", coordinator, "--run-id", "elastic-test",
            "--max-restarts", "2", "--restart-backoff", "1.0",
            "--tmpdir", str(tmp_path / f"scratch{node_rank}"),
            "--", sys.executable, str(worker)])

    with concurrent.futures.ThreadPoolExecutor(max_workers=2) as pool:
        rcs = list(pool.map(agent, [0, 1]))
    assert rcs == [0, 0], rcs

    # Attempt 0 formed the full world before the induced crash...
    assert len(list(out_dir.glob("attempt0_rank*.json"))) == 2
    # ...and the restarted world (same run id) completed + converged.
    recs = [json.load(open(f)) for f in sorted(out_dir.glob("elastic*.json"))]
    assert {r["rank"] for r in recs} == {0, 1}
    assert all(r["attempt"] == 1 for r in recs), recs
    assert all(r["run_id"] == "elastic-test" for r in recs)
    assert all(r["last"] < r["first"] for r in recs)


MPI_WORKER = """
    import json, os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"

    import numpy as np

    from tpudist.comm import collectives
    from tpudist.runtime import bootstrap
    from tpudist.runtime.mpi_bootstrap import initialize_from_mpi

    # The real thing: MPI_COMM_WORLD rank/size, rank 0 picks the port,
    # bcast, then jax.distributed.initialize on the agreed coordinator
    # (demo_assume_started_with_mpiexec.py:35-50 semantics end to end).
    ctx = initialize_from_mpi()
    total = collectives.host_allreduce_sum(np.float64(ctx.process_id))
    assert float(total) == sum(range(ctx.num_processes))
    collectives.barrier()
    out = os.path.join(os.environ["OUT_DIR"], f"mpi{ctx.process_id}.json")
    json.dump({"rank": ctx.process_id, "world": ctx.num_processes,
               "source": ctx.launch_source}, open(out, "w"))
    bootstrap.shutdown()
"""


def _mpi_launcher():
    import shutil

    for exe in ("mpiexec", "mpirun"):
        path = shutil.which(exe)
        if path:
            return path
    return None


def _has_mpi4py():
    try:
        import mpi4py  # noqa: F401

        return True
    except ImportError:
        return False


@pytest.mark.skipif(
    _mpi_launcher() is None or not _has_mpi4py(),
    reason="needs an MPI launcher (mpiexec/mpirun) and mpi4py",
)
def test_mpiexec_bootstrap_end_to_end(tmp_path, monkeypatch):
    """Launch 2 ranks under the REAL mpiexec: exchange_coordinator picks
    and broadcasts the rendezvous over MPI, jax.distributed forms the
    world, a cross-process collective proves it (SURVEY.md §3.3 — 'use one
    fabric (MPI) to bootstrap another')."""
    import subprocess
    import textwrap as tw

    worker = tmp_path / "worker.py"
    worker.write_text(tw.dedent(MPI_WORKER))
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TPUDIST_", "SLURM_")) and k not in (
               "RANK", "WORLD_SIZE", "MASTER_ADDR", "NODE_RANK")}
    env.update({"OUT_DIR": str(out_dir), "PYTHONPATH": str(REPO)})
    launcher = _mpi_launcher()
    cmd = [launcher, "-np", "2", sys.executable, str(worker)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=300)
    if proc.returncode != 0 and "oversubscribe" in (
            proc.stdout + proc.stderr).lower():
        # OpenMPI refuses slots > cores by default on small hosts.
        cmd = [launcher, "-np", "2", "--oversubscribe",
               sys.executable, str(worker)]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=300)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    recs = [json.load(open(f)) for f in sorted(out_dir.glob("mpi*.json"))]
    assert {r["rank"] for r in recs} == {0, 1}
    assert all(r["world"] == 2 for r in recs)
    assert all(r["source"] == "mpi" for r in recs)


def test_agent_preemption_end_to_end(tmp_path):
    """The SLURM preemption shape, end to end (VERDICT r3 #5): SIGTERM the
    tpurun AGENT'S PROCESS GROUP (what `scancel`/requeue actually signals)
    while two gloo-rendezvous'd workers train `examples/demo.py` with
    checkpointing.  The agent must survive the signal, the workers must
    save one agreed `preempted`-stamped checkpoint (Orbax collective
    save), the agent must surface the outcome and exit 0 without
    restarting, and a `--resume` relaunch under the agent must complete
    the original budget."""
    import signal
    import subprocess
    import time

    ckdir = tmp_path / "ck"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TPUDIST_", "SLURM_", "OMPI_"))
           and k not in ("RANK", "WORLD_SIZE", "MASTER_ADDR", "NODE_RANK")}
    env.pop("XLA_FLAGS", None)  # one CPU device per worker process
    env.update({
        "JAX_PLATFORMS": "cpu",
        "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
        "PYTHONPATH": str(REPO),
        "TPUDIST_SYNC_EVERY": "16",  # prompt preemption boundaries
    })
    worker_cmd = [sys.executable, str(REPO / "examples" / "demo.py"),
                  "--dry_run", "--total_iterations", "2000000",
                  "--checkpoint_dir", str(ckdir),
                  "--checkpoint_every", "100000", "--seed", "0"]
    agent_cmd = [sys.executable, "-m", "tpudist.launch.run",
                 "--nprocs", "2", "--max-restarts", "2",
                 "--restart-backoff", "0.1",
                 "--tmpdir", str(tmp_path / "scratch"),
                 "--", *worker_cmd]
    # New session => the agent leads its own process group, and killpg
    # reaches agent + workers together — exactly what SLURM delivers.
    proc = subprocess.Popen(agent_cmd, env=env, cwd=str(tmp_path),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)
    try:
        # Readiness: metrics rows appear only once rank 0 iterates, which
        # is strictly after the workers installed their SIGTERM handlers.
        deadline = time.time() + 300
        while time.time() < deadline:
            rows = [p for p in tmp_path.glob("runs/**/metrics.jsonl")
                    if p.stat().st_size > 0]
            if rows:
                break
            assert proc.poll() is None, proc.communicate()[0][-3000:]
            time.sleep(0.5)
        else:
            raise AssertionError("training never produced a metrics row")
        time.sleep(2)  # let a few sync windows land
        os.killpg(proc.pid, signal.SIGTERM)
        out, _ = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
    assert proc.returncode == 0, out[-3000:]
    # The agent surfaced the preemption and did NOT treat it as a crash.
    assert "preemption: worker group saved and exited cleanly" in out, \
        out[-3000:]
    assert "restarting worker group" not in out, out[-3000:]
    # One agreed checkpoint with the preempted stamp.
    metas = sorted(ckdir.rglob("meta/metadata"))
    assert metas, f"no checkpoint written: {out[-3000:]}"
    meta = json.loads(metas[-1].read_text())
    assert meta.get("preempted") is True, meta
    saved_at = meta["iteration"]
    assert 0 < saved_at < 2000000

    # Resume under the agent to the original-budget shape.
    resume_cmd = [sys.executable, "-m", "tpudist.launch.run",
                  "--nprocs", "2", "--max-restarts", "0",
                  "--tmpdir", str(tmp_path / "scratch2"),
                  "--", sys.executable, str(REPO / "examples" / "demo.py"),
                  "--dry_run", "--total_iterations", str(saved_at + 32),
                  "--checkpoint_dir", str(ckdir),
                  "--checkpoint_every", "100000", "--resume",
                  "--seed", "0"]
    r = subprocess.run(resume_cmd, env=env, cwd=str(tmp_path),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
