"""Bootstrap contract tests — the rank-derivation matrix of SURVEY.md §3.1-3.3."""

from pathlib import Path

import pytest

from tpudist.runtime.bootstrap import (
    BootstrapError,
    ProcessContext,
    find_free_port,
    resolve_process_context,
)
from tpudist.runtime.mesh import MeshConfig, data_model_mesh, data_parallel_mesh, make_mesh
from tpudist.runtime.seeding import per_process_seed


@pytest.fixture(autouse=True)
def clean_env(monkeypatch):
    for var in (
        "TPUDIST_NUM_PROCESSES", "TPUDIST_PROCESS_ID", "TPUDIST_COORDINATOR",
        "RANK", "WORLD_SIZE", "LOCAL_RANK", "LOCAL_WORLD_SIZE",
        "MASTER_ADDR", "MASTER_PORT", "SLURM_PROCID", "SLURM_LOCALID",
        "SLURM_NTASKS", "NODE_RANK", "TASKS_PER_NODE",
        "OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE",
    ):
        monkeypatch.delenv(var, raising=False)
    yield


def test_single_process_default():
    ctx = resolve_process_context()
    assert ctx.launch_source == "single"
    assert ctx.num_processes == 1 and ctx.process_id == 0
    assert not ctx.is_distributed


def test_torchrun_contract(monkeypatch):
    monkeypatch.setenv("RANK", "3")
    monkeypatch.setenv("WORLD_SIZE", "8")
    monkeypatch.setenv("LOCAL_RANK", "3")
    monkeypatch.setenv("LOCAL_WORLD_SIZE", "4")
    monkeypatch.setenv("MASTER_ADDR", "node0")
    monkeypatch.setenv("MASTER_PORT", "2345")
    ctx = resolve_process_context()
    assert ctx.launch_source == "torchrun"
    assert ctx.process_id == 3 and ctx.num_processes == 8
    assert ctx.coordinator_address == "node0:2345"
    assert ctx.local_rank == 3 and ctx.local_world_size == 4


def test_slurm_procid_contract(monkeypatch):
    # demo.py:41 — global rank from SLURM_PROCID
    monkeypatch.setenv("WORLD_SIZE", "4")
    monkeypatch.setenv("SLURM_PROCID", "2")
    monkeypatch.setenv("SLURM_LOCALID", "0")
    monkeypatch.setenv("TASKS_PER_NODE", "2")
    monkeypatch.setenv("MASTER_ADDR", "head")
    ctx = resolve_process_context()
    assert ctx.launch_source == "slurm"
    assert ctx.process_id == 2 and ctx.num_processes == 4
    assert ctx.coordinator_address == "head:2345"  # default port parity


def test_slurm_node_rank_contract(monkeypatch):
    # demo.py:38-39 — global = NODE_RANK * TASKS_PER_NODE + SLURM_LOCALID
    monkeypatch.setenv("WORLD_SIZE", "4")
    monkeypatch.setenv("SLURM_PROCID", "0")  # deliberately wrong; must be ignored
    monkeypatch.setenv("SLURM_LOCALID", "1")
    monkeypatch.setenv("TASKS_PER_NODE", "2")
    monkeypatch.setenv("NODE_RANK", "1")
    monkeypatch.setenv("MASTER_ADDR", "head")
    monkeypatch.setenv("MASTER_PORT", "9999")
    ctx = resolve_process_context(use_node_rank=True)
    assert ctx.process_id == 3
    assert ctx.coordinator_address == "head:9999"


def test_mpi_contract_requires_coordinator(monkeypatch):
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "1")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "2")
    with pytest.raises(BootstrapError):
        resolve_process_context()
    monkeypatch.setenv("MASTER_ADDR", "head")
    ctx = resolve_process_context()
    assert ctx.launch_source == "mpi" and ctx.process_id == 1


def test_tpudist_contract_wins_over_torchrun(monkeypatch):
    monkeypatch.setenv("TPUDIST_NUM_PROCESSES", "2")
    monkeypatch.setenv("TPUDIST_PROCESS_ID", "1")
    monkeypatch.setenv("TPUDIST_COORDINATOR", "c:1234")
    monkeypatch.setenv("RANK", "0")
    monkeypatch.setenv("WORLD_SIZE", "8")
    ctx = resolve_process_context()
    assert ctx.launch_source == "tpudist"
    assert ctx.process_id == 1 and ctx.num_processes == 2


def test_missing_env_fails_fast(monkeypatch):
    # fail-fast guard parity (demo.py:31-33,47-48)
    monkeypatch.setenv("RANK", "0")
    monkeypatch.setenv("WORLD_SIZE", "2")
    with pytest.raises(BootstrapError):
        resolve_process_context()


def test_find_free_port():
    p = find_free_port()
    assert 0 < p < 65536


def test_per_process_seed():
    assert per_process_seed(100, process_id=3) == 103
    assert per_process_seed(None, process_id=0) >= 0


def test_mesh_shapes(devices):
    m = data_parallel_mesh()
    assert m.axis_names == ("data",) and m.devices.shape == (8,)
    m2 = data_model_mesh(model_size=2)
    assert m2.axis_names == ("data", "model") and m2.devices.shape == (4, 2)
    m4 = make_mesh(MeshConfig(data=-1, stage=2, seq=2, model=1))
    assert m4.devices.shape == (2, 2, 2, 1)


def test_mesh_config_validation():
    with pytest.raises(ValueError):
        MeshConfig(data=3, stage=1, seq=1, model=1).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(data=-1, stage=-1).resolve(8)


def test_hybrid_mesh_single_process_falls_back(devices):
    """Single-process: make_hybrid_mesh must equal the plain mesh layout
    (DCN placement only matters across hosts)."""
    from tpudist.runtime.mesh import MeshConfig, make_hybrid_mesh, make_mesh

    cfg = MeshConfig(data=-1, model=2)
    hybrid = make_hybrid_mesh(cfg)
    plain = make_mesh(cfg)
    assert hybrid.axis_names == plain.axis_names
    assert hybrid.devices.shape == plain.devices.shape
    assert (hybrid.devices == plain.devices).all()


def test_hybrid_mesh_forced_granules_layout(devices):
    """force_granules=k: every non-data axis stays inside one contiguous
    pseudo-host block; the data axis crosses blocks granule-major — the
    single-process stand-in for the DCN x ICI placement contract."""
    import numpy as np

    from tpudist.runtime.mesh import MeshConfig, make_hybrid_mesh

    m = make_hybrid_mesh(MeshConfig(data=4, model=2),
                         axis_names=("data", "model"), force_granules=2)
    assert m.devices.shape == (4, 2)
    granule = np.vectorize(lambda d: d.id // 4)(m.devices)
    # model axis (rows) never crosses a granule
    assert (granule.min(axis=1) == granule.max(axis=1)).all()
    # data axis visits both granules, granule-major (outer positions)
    assert list(granule[:, 0]) == [0, 0, 1, 1]
    # data axis not divisible by granules -> clear error
    with pytest.raises(ValueError, match="granule"):
        make_hybrid_mesh(MeshConfig(data=1, model=8),
                         axis_names=("data", "model"), force_granules=2)


class TestCompilationCache:
    """The cache rule: JAX's own variable places the cache from outside
    and then the program sets no directory; otherwise ONE fixed path
    inside the checkout."""

    @pytest.fixture()
    def cache_config(self, monkeypatch):
        """jax.config survives monkeypatch: restore it, so no later
        test's compiles land in a cache dir."""
        import jax

        monkeypatch.delenv("TPUDIST_COMPILATION_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        old = jax.config.jax_compilation_cache_dir
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        yield jax.config
        jax.config.update("jax_compilation_cache_dir", old)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)

    def test_variable_set_means_no_directory_set_in_code(
            self, cache_config, monkeypatch, tmp_path):
        import tpudist.runtime.bootstrap as bootstrap

        cache_config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "out"))
        monkeypatch.setattr(bootstrap, "_INITIALIZED_CTX", None)
        bootstrap.initialize()
        # untouched by initialize(), and nothing was created for it
        assert cache_config.jax_compilation_cache_dir == "sentinel"
        assert not (tmp_path / "out").exists()
        from tpudist.runtime import enable_compilation_cache

        assert enable_compilation_cache() == str(tmp_path / "out")

    def test_unset_means_the_fixed_path_inside_the_checkout(
            self, cache_config, monkeypatch):
        from tpudist.runtime import compilation_cache as cc

        repo = Path(__file__).resolve().parent.parent
        assert cc.DEFAULT_CACHE_DIR == repo / ".jax_cache"
        # never the home directory, a temp name, a pid or the time
        monkeypatch.setenv("HOME", "/nonexistent-home")
        monkeypatch.setenv("TMPDIR", "/nonexistent-tmp")
        first = cc.enable_compilation_cache()
        assert first == str(repo / ".jax_cache") == \
            cache_config.jax_compilation_cache_dir
        assert Path(first).is_dir()
        assert cc.enable_compilation_cache() == first  # two calls, one path
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()

    def test_off_switch(self, cache_config, monkeypatch):
        from tpudist.runtime import enable_compilation_cache

        cache_config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv("TPUDIST_COMPILATION_CACHE", "off")
        assert enable_compilation_cache() is None
        assert cache_config.jax_compilation_cache_dir == "sentinel"

    def test_unwritable_location_raises_instead_of_running_uncached(
            self, cache_config, monkeypatch, tmp_path):
        from tpudist.runtime import compilation_cache as cc

        blocker = tmp_path / "file"
        blocker.write_text("")
        monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", blocker / ".jax_cache")
        with pytest.raises(OSError):
            cc.enable_compilation_cache()
