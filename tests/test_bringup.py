"""Bring-up plumbing: chip_smoke.py's phases rehearsed at tiny size on
the CPU, the entry points that must refuse a run without a chip, the
compile-cache placement, and the native library's build key."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def metrics_on():
    from gpu_rscode_tpu.obs import metrics

    was = metrics.forced()
    metrics.force_enable()
    yield
    metrics.force_enable(was)


def _check_line(line, phase, nbytes):
    assert line["phase"] == phase and line["bytes"] == nbytes
    assert line["wall_s"] > 0 and line["first_dispatch_s"] > 0
    # auto resolves to bitplane off the chip; on it chip_smoke.main
    # requires ["pallas"].
    assert line["strategy"] == ["bitplane"], line


def test_smoke_file_phase_rehearsal(tmp_path, metrics_on):
    outputs = []
    size = 3 * 10 * 4096 + 777  # three segments and a ragged tail
    line = chip_smoke.phase_file(
        str(tmp_path), size=size, segment_bytes=10 * 4096, outputs=outputs
    )
    _check_line(line, "file_rs10_4_w8", size)
    chip_smoke.check_outputs(outputs, platform="cpu")
    with pytest.raises(AssertionError, match="lives on"):
        chip_smoke.check_outputs(outputs, platform="tpu")


def test_smoke_wide_phase_rehearsal(tmp_path, metrics_on):
    size = 3 * 20 * 4096 + 778
    line = chip_smoke.phase_wide(
        str(tmp_path), size=size, segment_bytes=10 * 4096,
        oracle_bytes=8192,
    )
    _check_line(line, "wide_rs10_4_w16", size)


def test_smoke_store_phase_rehearsal(tmp_path, metrics_on):
    line = chip_smoke.phase_store(
        str(tmp_path), encode_bytes=200_000, objects=24,
        object_bytes=5000, gets=6,
    )
    _check_line(line, "store_serve", 200_000 + 24 * 5000)


def test_smoke_mesh_phase_rehearsal(tmp_path, metrics_on):
    size = 3 * 10 * 4096 + 123
    line = chip_smoke.phase_mesh(
        str(tmp_path), n_devices=4, size=size, segment_bytes=10 * 4096
    )
    _check_line(line, "mesh_4chip", 2 * size)


@pytest.mark.parametrize("where", ["bench.py", "chip_smoke.py", "alone"])
def test_entry_points_refuse_without_a_chip(tmp_path, where):
    """On the CPU, bench.py and chip_smoke.py exit non-zero and print
    no result; so does chip_smoke.py with the rest of the repo absent."""
    cwd = REPO
    script = where
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd, script = str(tmp_path), "chip_smoke.py"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_compile_cache_env_unset_uses_repo_dir(monkeypatch):
    import jax

    from gpu_rscode_tpu.utils import backend

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert backend.COMPILE_CACHE_DIR == want
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert backend.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", saved[1])
    # Before jax is imported the setting rides the environment.
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = (
        "from gpu_rscode_tpu.utils.backend import configure_compile_cache\n"
        "configure_compile_cache()\n"
        "import jax\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()
    assert out[-1] == want


def test_compile_cache_env_set_is_left_alone(monkeypatch, tmp_path):
    import jax

    from gpu_rscode_tpu.utils import backend

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    assert backend.configure_compile_cache() == str(tmp_path)
    assert (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs) == before


def test_native_rebuilds_for_other_source_or_host(monkeypatch, tmp_path):
    """The library is keyed on the source hash and the host's CPU flags:
    an unkeyed or differently keyed build is never loaded."""
    from gpu_rscode_tpu import native

    if not native.available():
        pytest.skip("no C++ toolchain here")
    real_so = native.lib_path()
    src = tmp_path / "rs_native.cpp"
    shutil.copyfile(native._SRC, src)
    (tmp_path / "librs_native.so").write_bytes(b"stale, unkeyed")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    built = []

    def fake_build(so):
        built.append(so)
        shutil.copyfile(real_so, so)
        return so

    monkeypatch.setattr(native, "_build", fake_build)

    def load():
        monkeypatch.setattr(native, "_lib", None)
        native.get_lib()
        return built[-1]

    first = load()
    assert built == [first] and os.path.dirname(first) == str(tmp_path)
    assert load() == first and len(built) == 1  # same source, same host
    monkeypatch.setattr(native, "_host_cpu_flags", lambda: "other host")
    assert load() != first and len(built) == 2
    src.write_text(src.read_text() + "\n// edited\n")
    load()
    assert len(set(built)) == 3
    np.testing.assert_array_equal(
        native.gemm(np.eye(2, dtype=np.uint8), np.arange(6, dtype=np.uint8)
                    .reshape(2, 3)),
        np.arange(6, dtype=np.uint8).reshape(2, 3),
    )
