"""``chip_smoke.py`` at tiny sizes on the CPU: its phases drive the same
entry points the chip run does (so a wrong path, argument or check fails
here first), its checks bite, and off a TPU it refuses to run at all."""
import functools
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke
from repro.ggpu.engine import GGPUConfig
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
from repro.registry import BENCHES
from repro.registry.benches import ordered_names

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def benches():
    return {n: BENCHES.get(n).build(*BENCHES.get(n).smoke_sizes)
            for n in ordered_names()}


@pytest.fixture(scope="module")
def pins(smoke, benches):
    """Pins for the tiny benches, from single-launch ``run_kernel``."""
    return {smoke.cfg_key(c): smoke.launch_pins(benches, 2, c)
            for c in (GGPUConfig(n_cus=8), GGPUConfig(n_cus=1),
                      GGPUConfig(n_cus=8, memsys="banked"))}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_a_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert out == ""


def test_phase_a_served_path_matches_single_launches(smoke, benches, pins):
    """Every bench folds into one cohort per memsys, and the fleet's
    placement keeps each launch's cycles/stats equal to running it
    alone."""
    report = smoke.phase_a(benches, pins, launches=2)
    assert report["launches_served"] == 3 * 2 * len(benches)
    assert sum(report["fleet"]["placement"].values()) == 2 * len(benches)
    assert len(report["checks"]) == 3
    assert report["compiles"] > 0 and report["compile_s"] > 0


def test_phase_a_fails_on_a_wrong_pin(smoke, benches, pins):
    bad = json.loads(json.dumps(pins))
    bad["8cu/shared"]["copy"][1][0] += 1          # one cycle off
    with pytest.raises(smoke.SmokeError, match="copy/1 on 8cu/shared"):
        smoke.phase_a({"copy": benches["copy"]}, bad, launches=2,
                      memsystems=("shared",))


def test_phase_b_serves_and_compares_logits(smoke, monkeypatch):
    """The served model path with the flash kernel interpreted (the CPU
    cannot compile it): every request answered, logits within bound."""
    import repro.kernels.flash_attention as fa
    monkeypatch.setattr(fa, "flash_attention",
                        functools.partial(fa.flash_attention, interpret=True))
    report = smoke.phase_b(get_smoke("smollm-360m"), prompt_lens=(5, 12),
                           max_new=4)
    assert report["launches_served"] == 4
    assert report["f32_flash_vs_jnp_max_diff"] \
        <= 1e-4 * report["logit_max_abs"]
    assert all(5 <= n <= 12 for n in report["prompt_lens"])


def test_phase_b_catches_a_wrong_kernel(smoke, monkeypatch):
    """A flash kernel that ignores the causal mask fails the float32
    comparison."""
    import repro.kernels.flash_attention as fa
    kernel = fa.flash_attention

    def acausal(q, k, v, **kw):
        return kernel(q, k, v, **{**kw, "causal": False}, interpret=True)

    monkeypatch.setattr(fa, "flash_attention", acausal)
    with pytest.raises(smoke.SmokeError, match="float32 flash vs jnp"):
        smoke.phase_b(get_smoke("smollm-360m"), prompt_lens=(5, 12),
                      max_new=2)


SHARDED = textwrap.dedent("""
    import importlib.util, json, sys
    import jax
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.registry import BENCHES
    benches = {n: BENCHES.get(n).build(*BENCHES.get(n).smoke_sizes)
               for n in ("mat_mul", "copy", "xcorr", "reduction")}
    report = smoke.phase_sharded(benches["xcorr"], jax.devices()[:4], n=8,
                                 fleet_benches=benches)
    print(json.dumps(report))
""")


def test_phase_sharded_on_four_host_devices():
    """The ``--chips 4`` phase on four forced host devices: one 4-way
    dispatch bit-identical to one device, and a fleet with one config on
    each device."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", SHARDED,
                           str(ROOT / "chip_smoke.py")], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["chips"] == 4 and report["launches_served"] == 2 * 8 + 8
    assert all(report["fleet_placement"].values())


def test_compile_cache_dir_defaults_to_the_repo():
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR == ROOT / ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
