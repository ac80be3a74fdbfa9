"""What must stay true of ``chip_smoke.py`` and the helpers it leans on,
checked where there is no chip: it refuses a CPU before building
anything, the compile cache has one fixed home unless the environment
names another, and ``dryrun_multichip`` raises on too few devices
instead of emulating them."""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(code_or_args, env_extra=None, env_drop=(), cwd=REPO):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    env.update(env_extra or {})
    args = code_or_args if isinstance(code_or_args, list) \
        else ["-c", code_or_args]
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu_before_building_a_model():
    proc = _run([str(REPO / "chip_smoke.py")])
    assert proc.returncode not in (0, 4), proc.stdout + proc.stderr
    assert "backend cpu" in proc.stdout
    assert "FAIL" in proc.stdout and "'cpu'" in proc.stdout
    # no phase ran, no model was built, no result was printed
    assert "phase" not in proc.stdout
    assert '"ok"' not in proc.stdout
    assert "bigdl_tpu" not in proc.stderr


_CACHE_PROBE = """
import jax
from bigdl_tpu.utils.compile_cache import enable_compile_cache
before = jax.config.jax_compilation_cache_dir
path = enable_compile_cache()
import json
print(json.dumps([before, jax.config.jax_compilation_cache_dir, path]))
"""


def test_compile_cache_leaves_the_environments_directory_alone(tmp_path):
    named = str(tmp_path / "named_from_outside")
    proc = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": named})
    assert proc.returncode == 0, proc.stderr
    before, after, path = json.loads(proc.stdout.strip().splitlines()[-1])
    # jax read the variable itself; the helper set nothing
    assert before == after == path == named


def test_compile_cache_default_is_one_place_in_the_checkout(tmp_path):
    # two processes, two working directories, one answer: a child started
    # somewhere else, and this process (which only computes the path —
    # conftest.py already chose this run's directory)
    from bigdl_tpu.utils import compile_cache

    want = str(REPO / ".cache" / "jax")
    proc = _run(_CACHE_PROBE, env_drop=("JAX_COMPILATION_CACHE_DIR",),
                cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr
    _, after, path = json.loads(proc.stdout.strip().splitlines()[-1])
    assert after == path == want
    assert str(compile_cache._CHECKOUT / ".cache" / "jax") == want


def test_dryrun_multichip_raises_on_too_few_devices(monkeypatch, capsys):
    import jax
    import pytest

    import __graft_entry__ as graft

    one = jax.devices()[:1]             # the suite itself has 8 virtual ones
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: one)
    with pytest.raises(RuntimeError, match="needs 8 devices"):
        graft.dryrun_multichip(8)
    assert "OK" not in capsys.readouterr().out
