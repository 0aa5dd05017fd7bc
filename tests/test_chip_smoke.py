"""chip_smoke.py: refuses to run without a TPU, and its phases work at smoke size.

The script itself only ever runs at minicpm-2b's published widths on a chip;
here its phase functions are driven on the CPU with a small :class:`Size`
(kernels in interpret mode), and the four-chip phase on four virtual CPU
devices in a subprocess.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


SMALL = dict(
    matmuls=((4, 64, 96), (40, 64, 96)), attention=(1, 2, 64, 16),
    lanes=2, pool_seq=64, segment_len=8, requests=3, prompt_lens=(5, 12),
    new_tokens=(8, 12), train_batch=2, train_seq=32, train_steps=2,
    cannon_n=64, cannon_blocks=2,
)


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def small(smoke):
    cfg = get_config("minicpm-2b", smoke=True)
    return smoke.Size(serve_cfg=cfg, train_cfg=cfg, **SMALL)


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**os.environ, **env})


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_exits_nonzero_without_tpu_or_repo(tmp_path, where):
    """No TPU (JAX held to the CPU), or a directory holding nothing of the
    repo but the script: a non-zero exit and no result line."""
    if where == "alone":
        shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
        cwd, env = tmp_path, {"PYTHONPATH": ""}
    else:
        cwd, env = ROOT, {}
    out = _run([SCRIPT.name], cwd, JAX_PLATFORMS="cpu", **env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(tmp_path, env_dir):
    """Importing the library leaves JAX's compilation cache off; the entry
    points' helper keeps ``JAX_COMPILATION_CACHE_DIR`` where it is set, else
    points JAX at the fixed ``<repo>/.jax_cache``."""
    code = textwrap.dedent("""
        import jax
        import repro.launch.engine, repro.launch.serve, repro.launch.train
        print(jax.config.jax_compilation_cache_dir)
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """)
    env = {"PYTHONPATH": str(ROOT / "src")}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    run = subprocess.run(
        [sys.executable, "-c", code], cwd=tmp_path, capture_output=True,
        text=True, timeout=120,
        env={**{k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"},
             "JAX_PLATFORMS": "cpu", **env})
    assert run.returncode == 0, run.stderr[-2000:]
    on_import, chosen, after = run.stdout.split()
    if env_dir:
        # JAX reads the variable itself; nothing is set in code
        assert chosen == after == str(tmp_path / env_dir)
    else:
        assert on_import == "None"
        assert chosen == after == str(ROOT / ".jax_cache")


def test_phases_at_smoke_size(smoke, small):
    rows = smoke.kernel_phase(small, seed=0, on_chip=False)
    assert [r["worst_over_tol"] <= 1.0 for r in rows] == [True] * 3
    serve = smoke.serve_phase(small, seed=0)
    assert serve["tokens"] >= small.requests * small.new_tokens[0]
    assert serve["prefill_forward_rel_l2"] <= 5e-2
    train = smoke.train_phase(small, seed=0)
    assert len(train["losses"]) == small.train_steps


def test_phase_failure_is_reported(smoke, small):
    """A check that fails raises, so main() exits non-zero."""
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.device_phase(1)


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT / "tests")!r})
        from test_chip_smoke import SMALL, _load
        from repro.configs import get_config
        smoke = _load()
        cfg = get_config("minicpm-2b", smoke=True)
        out = smoke.four_chip_phase(
            smoke.Size(serve_cfg=cfg, train_cfg=cfg, **SMALL), seed=0)
        print("FOUR OK", out["cannon_max_abs_err"])
    """)
    out = _run(["-c", code], ROOT, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR OK" in out.stdout
