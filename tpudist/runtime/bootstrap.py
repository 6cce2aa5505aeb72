"""Process bootstrap: the rank/world-size contract and distributed init.

This is the TPU-native replacement for the reference's dual bootstrap paths
(``demo.py:19-73``): torchrun env vars (``WORLD_SIZE``/``LOCAL_WORLD_SIZE``/
``LOCAL_RANK``/``RANK``), raw-scheduler env vars (``SLURM_PROCID`` or
``NODE_RANK * TASKS_PER_NODE + SLURM_LOCALID``, ``demo.py:36-41``), and the
MPI bootstrap (``demo_assume_started_with_mpiexec.py:29-50``).  All three
rendezvous modes of the reference (c10d store / explicit tcp:// / env seeded
by MPI broadcast, SURVEY.md §5.8) collapse onto one primitive here:
``jax.distributed.initialize(coordinator_address, num_processes, process_id)``.

Resolution priority (first match wins):

1. explicit arguments to :func:`resolve_process_context`
2. tpudist launcher contract: ``TPUDIST_COORDINATOR`` / ``TPUDIST_NUM_PROCESSES``
   / ``TPUDIST_PROCESS_ID`` (set by ``launch/tpurun``)
3. torchrun-style contract: ``MASTER_ADDR``/``MASTER_PORT`` + ``RANK`` +
   ``WORLD_SIZE`` (and ``LOCAL_RANK``/``LOCAL_WORLD_SIZE``)
4. SLURM contract: ``MASTER_ADDR``/``MASTER_PORT`` + ``WORLD_SIZE`` +
   (``NODE_RANK``×``TASKS_PER_NODE``+``SLURM_LOCALID`` when ``use_node_rank``,
   else ``SLURM_PROCID``) — the ``demo.py:35-49`` contract verbatim
5. OpenMPI/PMI contract: ``OMPI_COMM_WORLD_RANK``/``OMPI_COMM_WORLD_SIZE``
   (+ optional mpi4py hostname/port broadcast, see
   ``tpudist.runtime.mpi_bootstrap``)
6. single-process default (no distributed init)
"""

from __future__ import annotations

import dataclasses
import os
import random
import socket
import sys
import time
from typing import Callable, Optional


class BootstrapError(RuntimeError):
    """A launch contract was detected but is incomplete/inconsistent."""


@dataclasses.dataclass(frozen=True)
class ProcessContext:
    """Everything a rank needs to know about its place in the job."""

    process_id: int
    num_processes: int
    coordinator_address: Optional[str]  # "host:port" or None for single-process
    local_rank: int
    local_world_size: int
    launch_source: str  # explicit | tpudist | torchrun | slurm | mpi | single

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def _env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError as e:
        raise BootstrapError(f"env var {name}={v!r} is not an integer") from e


def _require(name: str) -> str:
    v = os.environ.get(name)
    if v is None or v == "":
        # Mirrors the reference's fail-fast env checks (demo.py:31-33,47-48).
        raise BootstrapError(
            f"required env var {name} is not set for this launch contract"
        )
    return v


def _coordinator_from_master_env(default_port: int = 2345) -> str:
    addr = _require("MASTER_ADDR")
    port = _env_int("MASTER_PORT", default_port)
    return f"{addr}:{port}"


def resolve_process_context(
    *,
    process_id: Optional[int] = None,
    num_processes: Optional[int] = None,
    coordinator_address: Optional[str] = None,
    use_node_rank: bool = False,
) -> ProcessContext:
    """Resolve (process_id, num_processes, coordinator) from args or env.

    ``use_node_rank`` mirrors the reference's ``--use_node_rank`` flag
    (``argument_parser.py:16-19``, consumed at ``demo.py:38-41``).
    """
    if num_processes is not None:
        if process_id is None:
            raise BootstrapError("explicit num_processes requires explicit process_id")
        if num_processes > 1 and coordinator_address is None:
            raise BootstrapError(
                "explicit multi-process launch requires coordinator_address"
            )
        return ProcessContext(
            process_id=process_id,
            num_processes=num_processes,
            coordinator_address=coordinator_address,
            local_rank=_env_int("LOCAL_RANK", 0) or 0,
            local_world_size=_env_int("LOCAL_WORLD_SIZE", 1) or 1,
            launch_source="explicit",
        )

    env = os.environ
    # 2. tpudist launcher contract.
    if "TPUDIST_NUM_PROCESSES" in env:
        n = _env_int("TPUDIST_NUM_PROCESSES")
        pid = _env_int("TPUDIST_PROCESS_ID")
        if n is None:
            raise BootstrapError("TPUDIST_NUM_PROCESSES is set but empty")
        if pid is None:
            raise BootstrapError("TPUDIST_NUM_PROCESSES set but TPUDIST_PROCESS_ID missing")
        coord = env.get("TPUDIST_COORDINATOR")
        if n > 1 and not coord:
            raise BootstrapError("TPUDIST_COORDINATOR required for multi-process launch")
        return ProcessContext(
            process_id=pid,
            num_processes=n,
            coordinator_address=coord,
            local_rank=_env_int("TPUDIST_LOCAL_RANK", 0) or 0,
            local_world_size=_env_int("TPUDIST_LOCAL_WORLD_SIZE", 1) or 1,
            launch_source="tpudist",
        )

    # 3. torchrun-style contract (reference demo.py:25-34 reads WORLD_SIZE/
    #    LOCAL_WORLD_SIZE/LOCAL_RANK under --torchrun).
    if "RANK" in env and "WORLD_SIZE" in env:
        n = _env_int("WORLD_SIZE")
        pid = _env_int("RANK")
        if n is None or pid is None:
            raise BootstrapError("RANK/WORLD_SIZE are set but empty")
        coord = _coordinator_from_master_env() if n > 1 else None
        return ProcessContext(
            process_id=pid,
            num_processes=n,
            coordinator_address=coord,
            local_rank=_env_int("LOCAL_RANK", 0) or 0,
            local_world_size=_env_int("LOCAL_WORLD_SIZE", 1) or 1,
            launch_source="torchrun",
        )

    # 4. SLURM contract (reference demo.py:35-49).
    if "SLURM_PROCID" in env or ("WORLD_SIZE" in env and "SLURM_LOCALID" in env):
        n = _env_int("WORLD_SIZE", _env_int("SLURM_NTASKS"))
        if n is None:
            raise BootstrapError("SLURM launch detected but WORLD_SIZE/SLURM_NTASKS unset")
        local_rank = _env_int("SLURM_LOCALID", 0) or 0
        local_world = _env_int("TASKS_PER_NODE", _env_int("SLURM_NTASKS_PER_NODE", 1)) or 1
        if use_node_rank:
            # demo.py:38-39 — global = NODE_RANK * local_world + local_rank
            node_rank = _env_int("NODE_RANK")
            if node_rank is None:
                raise BootstrapError("--use_node_rank requires NODE_RANK")
            pid = node_rank * local_world + local_rank
        else:
            pid = _env_int("SLURM_PROCID")  # demo.py:41
            if pid is None:
                raise BootstrapError("SLURM launch without SLURM_PROCID")
        coord = _coordinator_from_master_env() if n > 1 else None
        return ProcessContext(
            process_id=pid,
            num_processes=n,
            coordinator_address=coord,
            local_rank=local_rank,
            local_world_size=local_world,
            launch_source="slurm",
        )

    # 5. OpenMPI contract (mpiexec-started; demo_assume_started_with_mpiexec.py).
    if "OMPI_COMM_WORLD_RANK" in env:
        n = _env_int("OMPI_COMM_WORLD_SIZE")
        pid = _env_int("OMPI_COMM_WORLD_RANK")
        if n is None or pid is None:
            raise BootstrapError("OMPI_COMM_WORLD_RANK/SIZE are set but empty")
        coord = None
        if n > 1:
            # The coordinator address must have been agreed on out-of-band —
            # either by the mpi4py broadcast helper
            # (tpudist.runtime.mpi_bootstrap.exchange_coordinator) or by env.
            if "MASTER_ADDR" in env:
                coord = _coordinator_from_master_env()
            else:
                raise BootstrapError(
                    "MPI launch detected; call "
                    "tpudist.runtime.mpi_bootstrap.exchange_coordinator() first "
                    "or set MASTER_ADDR/MASTER_PORT"
                )
        return ProcessContext(
            process_id=pid,
            num_processes=n,
            coordinator_address=coord,
            local_rank=_env_int("OMPI_COMM_WORLD_LOCAL_RANK", 0) or 0,
            local_world_size=_env_int("OMPI_COMM_WORLD_LOCAL_SIZE", 1) or 1,
            launch_source="mpi",
        )

    # 6. single-process default.
    return ProcessContext(
        process_id=0,
        num_processes=1,
        coordinator_address=None,
        local_rank=0,
        local_world_size=1,
        launch_source="single",
    )


def find_free_port() -> int:
    """Pick a free TCP port (reference ``_find_free_port``,
    ``demo_assume_started_with_mpiexec.py:20-27``)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("", 0))
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        return s.getsockname()[1]


_INITIALIZED_CTX: Optional[ProcessContext] = None


def _retry_with_backoff(
    fn: Callable[[int], "object"],
    *,
    retries: int,
    backoff_s: float,
    what: str,
    retry_on: tuple = (Exception,),
    sleep: Callable[[float], None] = time.sleep,
    rng: Callable[[], float] = random.random,
):
    """Run ``fn(attempt)`` with up to ``retries`` retries on ``retry_on``
    failures, sleeping a jittered exponential backoff between attempts:
    ``backoff_s * 2**attempt * (0.5 + rng())`` — the jitter (0.5x–1.5x)
    decorrelates a whole worker group hammering a recovering coordinator
    at the same instant.  KeyboardInterrupt/SystemExit (and anything not
    in ``retry_on``) pass through.  Shared by distributed init and the
    checkpoint manager's save path."""
    attempt = 0
    while True:
        try:
            return fn(attempt)
        except (KeyboardInterrupt, SystemExit):
            raise
        except retry_on as e:  # noqa: BLE001 — bounded by `retries`
            if attempt >= retries:
                raise
            delay = backoff_s * (2 ** attempt) * (0.5 + rng())
            from tpudist import telemetry

            telemetry.event("retry", what=what, attempt=attempt,
                            error=type(e).__name__, backoff_s=round(delay, 3))
            print(
                f"[tpudist.retry] {what} failed "
                f"(attempt {attempt + 1}/{retries + 1}): "
                f"{type(e).__name__}: {e}; retrying in {delay:.1f}s",
                file=sys.stderr, flush=True,
            )
            sleep(delay)
            attempt += 1


def initialize(
    ctx: Optional[ProcessContext] = None,
    *,
    use_node_rank: bool = False,
    initialization_timeout_s: int = 3600,
    init_retries: Optional[int] = None,
    init_backoff_s: Optional[float] = None,
) -> ProcessContext:
    """Bring up the JAX coordination service for this process.

    Replaces ``dist.init_process_group`` (``demo.py:27,49``).  The reference's
    1-hour init timeout (``demo.py:27``) is preserved as
    ``initialization_timeout_s``.  Idempotent: a second call returns the
    context from the first.

    ``jax.distributed.initialize`` is retried with jittered exponential
    backoff on transient coordinator failures (a worker restarted by
    ``tpurun`` often races the coordinator's own restart): ``init_retries``
    retries (default ``TPUDIST_INIT_RETRIES`` or 3) starting at
    ``init_backoff_s`` (default ``TPUDIST_INIT_BACKOFF_S`` or 1.0s).

    The whole bring-up is one ``init`` telemetry span, in a single
    process too: the coordination service and then the accelerator
    client (``jax.local_devices()``, which costs nothing if the client
    is already up).
    """
    global _INITIALIZED_CTX
    if _INITIALIZED_CTX is not None:
        return _INITIALIZED_CTX
    # Persistent XLA compilation cache: a compile that succeeded once is
    # not re-paid (see tpudist/runtime/compilation_cache.py for where it
    # lives and how to place it from outside).
    from tpudist.runtime.compilation_cache import enable_compilation_cache

    enable_compilation_cache()
    if ctx is None:
        ctx = resolve_process_context(use_node_rank=use_node_rank)
    # Chaos harness: honor TPUDIST_FAULT from the earliest runtime seam;
    # telemetry starts here too so the init span lands in the same session
    # the training loop records into.
    from tpudist import telemetry
    from tpudist.runtime import faults
    from tpudist.telemetry import names

    faults.arm_from_env()
    telemetry.ensure_started()
    import jax

    # one ``init`` span for the whole bring-up, single process or many:
    # the coordination service (retried) and then the accelerator client,
    # which ``jax.local_devices()`` brings up if nothing has yet
    with telemetry.span(names.INIT, world=ctx.num_processes,
                        source=ctx.launch_source):
        if ctx.is_distributed:
            from tpudist.utils.envutil import env_float

            if init_retries is None:
                init_retries = max(
                    0, int(env_float("TPUDIST_INIT_RETRIES", 3)))
            if init_backoff_s is None:
                init_backoff_s = env_float("TPUDIST_INIT_BACKOFF_S", 1.0)

            def _attempt(attempt: int) -> None:
                faults.inject_init(attempt)
                if attempt > 0:
                    # A failed connect leaves jax's global distributed
                    # state half-initialized (State.initialize sets
                    # .client BEFORE connect()), so a bare retry would
                    # raise 'should only be called once' forever.
                    # shutdown() clears it and is a documented no-op when
                    # nothing is running.
                    jax.distributed.shutdown()
                jax.distributed.initialize(
                    coordinator_address=ctx.coordinator_address,
                    num_processes=ctx.num_processes,
                    process_id=ctx.process_id,
                    initialization_timeout=initialization_timeout_s,
                )

            _retry_with_backoff(
                _attempt, retries=init_retries, backoff_s=init_backoff_s,
                what=f"jax.distributed.initialize({ctx.coordinator_address})",
            )
        jax.local_devices()
    _INITIALIZED_CTX = ctx
    return ctx


def shutdown() -> None:
    """Tear down the coordination service.

    Replaces ``dist.barrier(); dist.destroy_process_group()``
    (``demo.py:177-178``).  The barrier is implicit: ``jax.distributed
    .shutdown`` synchronizes with the coordination service.
    """
    global _INITIALIZED_CTX
    if _INITIALIZED_CTX is not None and _INITIALIZED_CTX.is_distributed:
        import jax

        jax.distributed.shutdown()
    _INITIALIZED_CTX = None
