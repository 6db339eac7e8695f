"""chip_smoke.py at toy size on the CPU: its phases run the served path
with the Pallas kernels under the interpreter, and the script itself
refuses to run, before it builds a model, where JAX finds no TPU."""
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

from repro.config.base import DiffusionConfig
from repro.models.efficientnet import DiscriminatorConfig
from repro.serving.cluster import ClusterRuntime

ROOT = pathlib.Path(__file__).resolve().parents[1]
TOY = DiffusionConfig(name="toy", image_size=8, in_channels=4,
                      base_channels=8, channel_mults=(1,), num_res_blocks=1,
                      attn_resolutions=(8,), num_heads=2, num_steps=2,
                      text_dim=16)
TOY_DISC = DiscriminatorConfig(stages=((16, 1, 1, 1), (24, 1, 2, 4)),
                               head_channels=32)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _conserved(rec):
    return rec["total"] == (rec["completed"] + rec["shed_admission"]
                            + rec["dropped_predictive"]
                            + rec["dropped_deadline"])


def test_phases_at_toy_size_interpret(smoke):
    cascade = smoke.build_cascade(TOY, seed=0, disc=TOY_DISC)
    assert cascade.disc_cfg.in_channels == TOY.in_channels
    serving = smoke.serving_config(2, (1, 4), kernel_impl="interpret")
    runtime = ClusterRuntime(cascade, serving)
    parity = smoke.phase_parity(cascade, seed=0)
    assert len(parity["errors"]) == 4
    b = smoke.phase_tiers(runtime, seed=0)
    assert b["impl"] == "interpret"
    assert sorted(b["wall_s"]) == ["disc/b1", "disc/b4", "tier0/b1",
                                   "tier0/b4", "tier1/b1", "tier1/b4"]
    assert b["run_batch"]["stage_index"] == [1, 1, 1, 1]
    # one program per bucket for each sampler and the discriminator
    assert b["compile_counts"] == [2, 2, 2]
    c = smoke.phase_serve(cascade, serving, seed=0, duration_s=3)
    assert c["completed"] > 0 and _conserved(c)
    assert c["discriminator_scored"] > 0
    assert c["compile_counts"] == [2, 2, 2]     # serving compiled nothing


def test_device_phase_at_toy_size_interpret(smoke):
    cascade = smoke.build_cascade(TOY, seed=1, disc=TOY_DISC)
    serving = smoke.serving_config(4, (4,), kernel_impl="interpret")
    d = smoke.phase_devices(cascade, serving, seed=1, duration_s=3)
    assert set(d["max_abs_diff_vs_device0"].values()) == {0.0}
    assert d["placements_per_device"] == {0: 3}
    assert d["serve"]["completed"] > 0 and _conserved(d["serve"])


def test_script_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
    assert '"phase"' not in r.stdout       # failed in phase A, built nothing


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The entry points' cache goes where JAX_COMPILATION_CACHE_DIR says,
    with no path set in code, and otherwise to .jax_cache/ at the root
    of the checkout."""
    import jax
    from repro.compile_cache import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
