"""chip_smoke.py off the chip, and the bring-up rules around it.

What only a TPU can show — that the trainer and the server start,
compile and answer there — is the script's own job (``python
chip_smoke.py`` through the chip tool).  What can be pinned here:

- it refuses any other platform with a non-zero exit and prints no
  ``"ok": true`` line (no CPU fallback);
- its phases run end to end at a tiny size through the ``size`` argument
  the phase functions take for this file alone (the script has no option
  for it);
- its last line is exactly the contract's JSON object;
- the compile-cache rule: ``JAX_COMPILATION_CACHE_DIR`` set → our code
  sets nothing; unset → one fixed path inside the checkout, the same in
  every process;
- the one platform gate is an error on a device that is neither a TPU
  nor the CPU;
- a parent that spawns benches stays off jax.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke.py lives at the repo root

import chip_smoke  # noqa: E402
from theanompi_tpu import cachedir  # noqa: E402
from theanompi_tpu import observability as obs  # noqa: E402


# ---------------------------------------------------------------------------
# no accelerator → non-zero, no result
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "script_alone"])
def test_chip_smoke_refuses_a_cpu_backend(alone, tmp_path):
    """As the driver runs it in the sandbox: it must fail, in the repo
    and in a directory that holds the script and nothing else."""
    cwd = REPO
    if alone:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not 'tpu'" in out.stderr


@pytest.fixture
def on_fake_tpus(monkeypatch):
    """``main()`` over N pretend TPU devices with every phase stubbed;
    returns the list the stubs append their names to."""

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    def install(n):
        ran = []
        monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [FakeTpu()] * n)
        for phase in ("train", "serve", "kernels", "multichip"):
            monkeypatch.setattr(
                chip_smoke, f"{phase}_phase",
                lambda seed=0, _p=phase: ran.append(_p) or {"seconds": 0.0},
            )
        return ran

    return install


def test_chip_smoke_last_line_is_the_contract(on_fake_tpus, capsys):
    """With a TPU under it and every phase green, the LAST stdout line
    is exactly ``{"ok": true, "device": {...}}`` — platform, kind and
    count as jax reports them — and carries nothing else."""
    ran = on_fake_tpus(1)
    assert chip_smoke.main([]) == 0
    assert ran == ["train", "serve", "kernels"]  # no 4-chip phase
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert all('"ok"' not in line for line in lines[:-1])
    # --chips 4 on a one-chip machine is refused before any phase
    ran.clear()
    assert chip_smoke.main(["--chips", "4"]) == 1
    assert ran == [] and '"ok"' not in capsys.readouterr().out


def test_chip_smoke_four_chip_option_runs_only_the_cross_chip_path(
    on_fake_tpus, capsys
):
    ran = on_fake_tpus(4)
    assert chip_smoke.main(["--chips", "4"]) == 0
    assert ran == ["multichip"]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["device"]["count"] == 4


# ---------------------------------------------------------------------------
# the phases, tiny, on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def compile_counters():
    obs.count_xla_compiles()


def test_train_phase_tiny(compile_counters):
    out = chip_smoke.train_phase(size="tiny")
    assert len(out["losses"]) == 6 and len(out["val_losses"]) == 2
    assert out["xla_programs"] > 0


def test_serve_phase_tiny(compile_counters):
    out = chip_smoke.serve_phase(size="tiny")
    assert out["requests"] == 8
    assert out["tokens"] == sum(chip_smoke.SERVE_CFG["tiny"]["new_tokens"])
    assert out["traces"]["verify"] == 0  # no speculative engine here


def test_kernels_phase_tiny(compile_counters):
    out = chip_smoke.kernels_phase(size="tiny")
    assert out["cases"] == len(out["max_abs_err"]) >= 16
    assert out["compiled"] == 0  # interpret mode on the CPU


def test_multichip_phase_tiny(compile_counters):
    """dp=4 against one device on the virtual CPU mesh: same first-step
    loss, same parameters after three steps, shards on four devices, an
    all-reduce in the dp step."""
    out = chip_smoke.multichip_phase(size="tiny")
    assert out["dp"] == 4 and out["all_reduces"]["dp"] > 0
    assert out["max_param_diff"] <= 2e-5


# ---------------------------------------------------------------------------
# the compile-cache rule
# ---------------------------------------------------------------------------

class _RecordingJax:
    """Stands in for the jax module: records every config.update."""

    def __init__(self):
        self.updates = []
        self.config = self

    def update(self, name, value):
        self.updates.append((name, value))


def test_cache_dir_variable_set_means_our_code_sets_nothing(monkeypatch):
    monkeypatch.setenv(cachedir.CACHE_ENV, "/somewhere/else")
    fake = _RecordingJax()
    assert cachedir.configure_compile_cache(fake) == "/somewhere/else"
    assert fake.updates == []


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(cachedir.CACHE_ENV, raising=False)
    fake = _RecordingJax()
    path = cachedir.configure_compile_cache(fake)
    assert path == os.path.join(REPO, ".jax_cache")
    assert ("jax_compilation_cache_dir", path) in fake.updates
    # identical across processes: nothing of the temp dir, the pid, the
    # host or the time goes into it
    env = {k: v for k, v in os.environ.items() if k != cachedir.CACHE_ENV}
    code = ("from theanompi_tpu.cachedir import repo_cache_dir; "
            "print(repo_cache_dir())")
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, capture_output=True,
            text=True, timeout=120, check=True,
            env=dict(env, PYTHONPATH=REPO, TMPDIR=tmp),
        ).stdout.strip()
        for cwd, tmp in ((REPO, "/tmp"), ("/", "/var/tmp"))
    }
    assert seen == {path}


def test_cache_dir_variable_reaches_jax_untouched_in_a_child():
    """The real jax in a fresh process: the exported directory is what
    jax's own config holds before and after our call."""
    code = (
        "import json, jax\n"
        "from theanompi_tpu.cachedir import configure_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "ret = configure_compile_cache(jax)\n"
        "print(json.dumps([before, jax.config.jax_compilation_cache_dir, ret]))"
    )
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR="/exported/by/the/driver")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, check=True, env=env,
    )
    assert json.loads(out.stdout.strip().splitlines()[-1]) == (
        ["/exported/by/the/driver"] * 3
    )


# ---------------------------------------------------------------------------
# one installation, one platform gate, one process per chip
# ---------------------------------------------------------------------------

def test_platform_gate_is_an_error_off_tpu_and_cpu(monkeypatch):
    from theanompi_tpu.ops import platform

    class Dev:
        def __init__(self, p):
            self.platform = p

    assert platform.on_tpu() is False  # this suite sits on the CPU
    monkeypatch.setattr(jax, "devices", lambda: [Dev("tpu")])
    assert platform.on_tpu() is True
    monkeypatch.setattr(jax, "devices", lambda: [Dev("gpu")])
    with pytest.raises(RuntimeError, match="'gpu'"):
        platform.on_tpu()


def test_parent_that_spawns_benches_stays_off_jax():
    """tuning/trials.py starts bench children; a parent that had touched
    jax would hold the chip against them."""
    code = (
        "import sys\n"
        "import theanompi_tpu.tuning.driver, theanompi_tpu.tuning.trials\n"
        "import theanompi_tpu.tuning.__main__\n"
        "assert 'jax' not in sys.modules, 'tuning imported jax'"
    )
    subprocess.run(
        [sys.executable, "-c", code], check=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO),
    )
