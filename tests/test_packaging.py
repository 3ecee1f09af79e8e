"""Installability: ``pip install -e .`` must produce working ``dtpu-*``
console scripts (reference parity: setup.py:58 installs ``dlrover-run``).

Installs into a throwaway venv with ``--system-site-packages`` (jax etc.
come from the host env; no network) and drives the entry points.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def install_venv(tmp_path_factory):
    vdir = tmp_path_factory.mktemp("pkgvenv")
    subprocess.run(
        [sys.executable, "-m", "venv", str(vdir)],
        check=True,
    )
    # make the host env's packages (jax, setuptools, …) visible: the test
    # runner may itself live in a venv, so --system-site-packages would
    # point at the wrong base — a .pth into the host's site-packages is
    # the offline-safe equivalent
    import site

    host_sites = "\n".join(
        p for p in site.getsitepackages() + [site.getusersitepackages()]
        if os.path.isdir(p)
    )
    venv_site = subprocess.run(
        [str(vdir / "bin" / "python"), "-c",
         "import site; print(site.getsitepackages()[0])"],
        capture_output=True, text=True, check=True,
    ).stdout.strip()
    with open(os.path.join(venv_site, "_host_site.pth"), "w") as f:
        f.write(host_sites + "\n")
    pip = vdir / "bin" / "pip"
    r = subprocess.run(
        [str(pip), "install", "--no-deps", "--no-build-isolation",
         "-e", REPO],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        pytest.fail(f"pip install -e failed:\n{r.stdout}\n{r.stderr}")
    return vdir


def test_console_scripts_installed(install_venv):
    for script in ("dtpu-run", "dtpu-master", "dtpu-operator", "dtpu-brain"):
        assert (install_venv / "bin" / script).exists(), script


def test_dtpu_run_help(install_venv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [str(install_venv / "bin" / "dtpu-run"), "--help"],
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert r.returncode == 0, r.stderr
    assert "--standalone" in r.stdout


def test_dtpu_master_help(install_venv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [str(install_venv / "bin" / "dtpu-master"), "--help"],
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert r.returncode == 0, r.stderr
