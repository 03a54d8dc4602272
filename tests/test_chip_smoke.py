"""Rehearsal of ``chip_smoke.py`` on the CPU, and where compiles are cached.

The phase functions run here at smoke width with the arena ops forced to
Pallas interpret mode (``$REPRO_ARENA_IMPL``, set by the test), so the
control flow and the checks the chip run makes are exercised without a
chip.  ``main()`` itself must refuse to run on anything but a TPU.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytest.importorskip("jax")

from repro.kernels.arena.ops import ENV_IMPL  # noqa: E402
from repro.launch.compile_cache import (  # noqa: E402
    DEFAULT_CACHE_DIR,
    ENV_CACHE_DIR,
)

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_host_without_tpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.delenv(ENV_IMPL, raising=False)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_paper_phase_rehearsal(chip_smoke, monkeypatch):
    monkeypatch.setenv(ENV_IMPL, "pallas_interpret")
    graphs = chip_smoke.paper_graphs()
    # the partial-conv accumulate cell and the elementwise-chain cell; the
    # 274-node network is left to the chip run
    rows = chip_smoke.paper_phase(
        {k: graphs[k] for k in ("swiftnet_cell_a", "darts_imagenet_cell")})
    assert [(r["graph"], r["fuse"]) for r in rows] == [
        ("swiftnet_cell_a", False), ("swiftnet_cell_a", True),
        ("darts_imagenet_cell", False), ("darts_imagenet_cell", True)]
    assert all(r["max_abs_err"] <= 1e-5 for r in rows)


def test_serving_phase_rehearsal(chip_smoke, monkeypatch):
    import repro.configs as configs

    monkeypatch.setenv(ENV_IMPL, "pallas_interpret")
    out = chip_smoke.serving_phase(configs.smoke("llama3.2-1b"),
                                   n_requests=2, prompt_len=8, gen=4)
    for mode in ("serial", "vmap"):
        assert out[mode]["n_served"] == 2
        assert out[mode]["n_tokens"] == 8


def test_serving_phase_catches_a_wrong_token(chip_smoke, monkeypatch):
    import repro.configs as configs

    real = chip_smoke._greedy_reference
    monkeypatch.setattr(chip_smoke, "_greedy_reference",
                        lambda *a: [t + 1 for t in real(*a)])
    with pytest.raises(chip_smoke.SmokeFailure, match="greedy decode"):
        chip_smoke.serving_phase(configs.smoke("llama3.2-1b"),
                                 n_requests=1, prompt_len=8, gen=2)


_WHERE = """
import jax
from repro.launch.compile_cache import configure_compile_cache
print(configure_compile_cache(), jax.config.jax_compilation_cache_dir)
"""

_COMPILE = """
import jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)))
"""


def _probe(script, env_dir=None):
    env = {k: v for k, v in os.environ.items() if k != ENV_CACHE_DIR}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env[ENV_CACHE_DIR] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_compile_cache_follows_the_environment(tmp_path):
    used, configured = _probe(_WHERE + _COMPILE, tmp_path)
    assert used == configured == str(tmp_path)
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_the_checkout():
    used, configured = _probe(_WHERE)
    assert used == configured == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
