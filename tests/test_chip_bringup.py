"""What the chip bring-up (ISSUE 21) fixed and must stay fixed, checkable on
the CPU: one process per chip, nothing that hides the device, the compile
cache placed from outside. The on-chip proof itself is ``chip_smoke.py``;
the kernels' TPU compilation is ``test_pallas_tpu_compile.py``."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, cwd=ROOT, **env):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str) else
            [sys.executable] + code_or_args)
    return subprocess.run(
        args, capture_output=True, text=True, timeout=300, cwd=cwd,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **env))


def test_importing_the_package_initialises_no_backend():
    # a parent that has touched JAX holds the chip; importing must not
    proc = _run(
        "import paddle_tpu, paddle_tpu.serving, paddle_tpu.models\n"
        "import paddle_tpu.hapi, paddle_tpu.distributed.launch.main\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, xb._backends\n"
        "print('no backend')")
    assert proc.returncode == 0 and "no backend" in proc.stdout, proc.stderr


def test_chip_smoke_needs_a_chip():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line without a TPU
    assert "no TPU" in proc.stderr


def test_chip_smoke_alone_prints_no_result(tmp_path):
    # a directory that holds chip_smoke.py and nothing else of the repo
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env={k: v for k, v in dict(os.environ, JAX_PLATFORMS="cpu").items()
             if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_places_never_stand_in_for_another_device():
    import paddle_tpu as paddle
    from paddle_tpu.framework import place

    before = place._current_device
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"
    with pytest.raises(ValueError, match="names no device"):
        paddle.TPUPlace(0).jax_device()  # not "any device": there is no TPU
    with pytest.raises(ValueError, match="names no device"):
        paddle.CPUPlace(99).jax_device()  # not clamped to the last one
    with pytest.raises(ValueError, match="names no device"):
        paddle.set_device("tpu")  # not answered with the CPU
    assert place._current_device is before
    assert paddle.set_device("cpu") == paddle.CPUPlace(0)
    place._current_device = before


def test_interpret_mode_is_only_the_context_manager(monkeypatch):
    from paddle_tpu.ops import pallas

    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")  # the old switch
    assert not pallas.interpret_requested()
    assert not pallas.is_available()  # CPU backend, nothing interpreted
    with pallas.interpret_mode():
        assert pallas.is_available()


def test_compile_cache_is_placed_from_outside(tmp_path):
    probe = ("from paddle_tpu.framework.compile_cache import "
             "enable_compile_cache\nprint(enable_compile_cache())")
    given = _run(probe, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert given.stdout.strip() == str(tmp_path / "cc"), given.stderr
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    default = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True,
        timeout=300, cwd=str(tmp_path),
        env=dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    path = default.stdout.strip()
    # one fixed path inside the checkout, whatever the working directory
    assert os.path.dirname(path) == os.path.join(ROOT, ".jax_cache"), path


def test_no_code_names_a_cache_directory_of_its_own():
    # tests may switch the cache off and back on; only compile_cache.py may
    # say where it lives, and nothing may put one under /tmp
    import re

    literal = re.compile(
        r"""jax_compilation_cache_dir["']\s*,\s*f?["']|/tmp/jax""")
    files = [os.path.join(ROOT, f)
             for f in ("chip_smoke.py", "__graft_entry__.py")]
    for base in ("paddle_tpu", "tools", "tests"):
        for dirpath, _, names in os.walk(os.path.join(ROOT, base)):
            files += [os.path.join(dirpath, f) for f in names
                      if f.endswith((".py", ".sh"))]
    hits = [os.path.relpath(f, ROOT) for f in files
            if f != os.path.abspath(__file__)
            and literal.search(open(f).read())]
    assert hits == [], hits


def test_launcher_pins_each_child_to_its_own_chip(monkeypatch):
    from paddle_tpu.distributed.launch.main import _chip_env

    for name in ("TPU_VISIBLE_CHIPS", "TPU_PROCESS_BOUNDS",
                 "TPU_PROCESS_ADDRESSES", "CLOUD_TPU_TASK_ID"):
        monkeypatch.delenv(name, raising=False)
    ports = [8476, 8477, 8478, 8479]
    envs = [_chip_env(i, 4, ports) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    # four one-chip processes of one host form the 2x2 slice
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert [e["TPU_PROCESS_PORT"] for e in envs] == [str(p) for p in ports]
    assert envs[2]["CLOUD_TPU_TASK_ID"] == "2"
    # any other count: isolated chips, never "every chip"
    two = _chip_env(1, 2, ports[:2])
    assert two["TPU_VISIBLE_CHIPS"] == "1"
    assert two["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert "TPU_PROCESS_ADDRESSES" not in two
    # what the caller set is the caller's
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "3")
    assert "TPU_VISIBLE_CHIPS" not in _chip_env(0, 4, ports)
