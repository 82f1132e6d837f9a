"""``chip_smoke.py`` on the CPU at a toy width, the compile-cache rule and
the virtual-CPU-device rule.

The script refuses every backend but a TPU, so the TEST bypasses its device
assertion (monkeypatch) — the program has no switch for it. What this pins is
that the phase functions still run end to end through the entry points they
drive, so a refactor cannot break the chip proof unnoticed; nothing here says
anything about the chip.
"""

import json
import os

import jax
import pytest

import chip_smoke
from simple_distributed_machine_learning_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def toy(monkeypatch, tmp_path):
    """Toy widths, the two chip-only assertions swapped for recorders, and
    the compile-cache directory (unused: conftest keeps the cache off) moved
    out of the checkout."""
    calls = {"kernel": []}

    def fake_require_tpu(count):
        d = jax.devices()[0]
        assert len(jax.devices()) >= count
        return {"platform": d.platform, "kind": d.device_kind,
                "count": count}

    def record_kernel(text, what):
        # on the CPU the kernel is interpreted, so the real guard must trip
        with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
            real_guard(text, what)
        calls["kernel"].append(what)

    real_guard = chip_smoke.assert_kernel_compiled
    monkeypatch.setattr(chip_smoke, "enable_compile_cache",
                        lambda: str(tmp_path / "cache"))
    monkeypatch.setattr(chip_smoke, "require_tpu", fake_require_tpu)
    monkeypatch.setattr(chip_smoke, "assert_kernel_compiled", record_kernel)
    monkeypatch.setattr(chip_smoke, "XL", chip_smoke.GPTConfig(
        vocab=64, seq_len=48, d_model=32, n_heads=4, n_layers=2))
    for name, v in dict(SERVE_SLOTS=3, SERVE_BLOCK=4, SERVE_CHUNK=8,
                        SERVE_REQUESTS=4, SERVE_PROMPTS=(6, 30),
                        SERVE_NEW=6).items():
        monkeypatch.setattr(chip_smoke, name, v)
    return calls



def test_one_chip_phases_run_at_toy_width(toy, capsys):
    assert chip_smoke.main([]) == 0
    out = capsys.readouterr().out
    for phase in ("device", "train", "serve", "cli"):
        assert f"== phase {phase} ok" in out
    assert "6/6 requests completed" in out          # the CLI serve phase
    assert toy["kernel"] == ["fused decode tick", "fused int8 decode tick"]
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}


def test_four_chip_phases_run_at_toy_width(toy, capsys):
    assert chip_smoke.main(["--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert "== phase pipeline-4 ok" in out
    assert "== phase replica-placement ok" in out
    assert "== phase train" not in out and "== phase serve" not in out
    assert json.loads(out.strip().splitlines()[-1])["device"]["count"] == 4


def test_no_tpu_is_a_failure_not_a_cpu_run(capsys):
    """Unpatched: the device phase refuses the CPU and no result line is
    printed."""
    with pytest.raises(chip_smoke.SmokeFailure, match="need a TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_compile_cache_env_dir_is_left_to_jax(cache_config, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_path_in_the_checkout(
        cache_config, monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for d in ("a", "b"):
        (tmp_path / d).mkdir()
        monkeypatch.chdir(tmp_path / d)
        seen.append(compile_cache.enable_compile_cache())
    assert seen[0] == seen[1] == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == seen[0]
    assert jax.config.jax_persistent_cache_min_compile_time_secs <= 1.0


@pytest.mark.parametrize("backend,n,ok", [("cpu", 8, True), ("cpu", 9, False),
                                          ("tpu", 4, False)])
def test_virtual_cpu_devices_keeps_only_a_live_cpu_backend(
        monkeypatch, backend, n, ok):
    """With the backend already up (conftest's 8 CPU devices) the request is
    met only by a CPU backend with enough devices; a live backend of another
    platform is an error even when it has the devices."""
    from simple_distributed_machine_learning_tpu.parallel import compat

    jax.devices()                                   # the backend is up
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if ok:
        compat.virtual_cpu_devices(n)
    else:
        with pytest.raises(RuntimeError):
            compat.virtual_cpu_devices(n)
