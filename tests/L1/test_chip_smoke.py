"""``chip_smoke.py`` and the pieces that keep a run from passing without
the chip (ISSUE 21): the preset refusals, a one-layer sentinel of the
trainer and server phases on the CPU (interpret mode), ``interpret_mode()``
raising on anything but ``cpu``/``tpu``, and the compile-cache helper.
The full tiny run — every phase, and ``--chips 4`` on the CPU mesh — is in
the slow lane."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import chip_smoke
from apex_tpu.utils import compile_cache, interpret_mode


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def _last_json(out: str) -> dict:
    """The run's summary: the second to last line.  The last line is the
    verdict the driver parses, and holds "ok" and "device" only."""
    summary, verdict = map(json.loads, out.strip().splitlines()[-2:])
    assert verdict == {"ok": True, "device": summary["device"]}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    return summary


def test_full_preset_refused_without_a_tpu(capsys):
    """``python chip_smoke.py`` where JAX finds no accelerator: non-zero,
    before any phase, and nothing on stdout."""
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err and "'cpu'" in out.err


def test_tiny_preset_refused_on_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    assert chip_smoke.main(["--preset", "tiny"]) != 0
    assert capsys.readouterr().out == ""


def test_more_chips_than_visible_fails_not_skips(capsys):
    assert chip_smoke.main(["--preset", "tiny", "--chips", "64"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "--chips 64" in out.err


def test_one_layer_sentinel_serves_on_cpu(capsys):
    """The server phase at one layer, in-process: the same code path the
    chip runs, Pallas interpreted.  (The trainer phase and the kernels
    ride the slow-lane full run.)"""
    rc = chip_smoke.main(["--preset", "tiny", "--phases", "server",
                          "--gpt-layers", "1"])
    out = capsys.readouterr().out
    assert rc == 0, out
    summary = _last_json(out)
    assert summary["ok"] is True
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": len(jax.devices())}
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["phases"]["server"]["depth"] == 1
    assert "DEPTH 1 layers" in out


def _run(*argv, devices=8):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py"),
                           *argv], capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=900)


def test_full_tiny_run_every_phase():
    """Slow lane: ``chip_smoke.py --preset tiny`` as a process — trainer,
    server, every kernel against its reference — exits 0 with the JSON
    summary last; and the bare command exits non-zero with empty stdout."""
    proc = _run("--preset", "tiny")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    summary = _last_json(proc.stdout)
    assert summary["ok"] and set(summary["phases"]) == {
        "trainer", "server", "kernels"}
    assert summary["phases"]["kernels"]["cases"] >= 19
    assert summary["phases"]["trainer"]["loss_last"] < \
        summary["phases"]["trainer"]["loss_first"]
    bare = _run()
    assert bare.returncode != 0 and bare.stdout == ""


def test_tiny_run_on_four_cpu_devices():
    """Slow lane: ``--chips 4`` on the CPU mesh — ZeRO dp=4 trainer, tp=4
    server with logits checked against tp=1."""
    proc = _run("--preset", "tiny", "--chips", "4")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    summary = _last_json(proc.stdout)
    assert summary["chips"] == 4 and set(summary["phases"]) == {
        "trainer", "server"}
    assert "tp=1 engine" in proc.stdout
    # fewer devices than asked for: a failure, not a skip
    short = _run("--preset", "tiny", "--chips", "4", devices=2)
    assert short.returncode != 0 and short.stdout == ""


# -- interpret_mode -----------------------------------------------------------

def test_interpret_mode_is_true_on_cpu_false_on_tpu(monkeypatch):
    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False


@pytest.mark.parametrize("platform", ["gpu", "rocm", "tpu_like"])
def test_interpret_mode_raises_on_an_unknown_platform(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    with pytest.raises(RuntimeError, match=platform):
        interpret_mode()


def test_interpret_mode_does_not_swallow_a_dead_backend(monkeypatch):
    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", dead)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        interpret_mode()


# -- compile cache helper -----------------------------------------------------

@pytest.fixture
def _cache_config():
    """Restore jax's cache config: the helper must never leak a cache
    directory into the suite's compile-count guards."""
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])


def test_cache_helper_leaves_jax_alone_when_the_env_places_it(
        monkeypatch, tmp_path, _cache_config):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_persistent_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(os.path.join(REPO, ".jax_cache"))


def test_cache_helper_defaults_to_the_checkout(monkeypatch, _cache_config):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = os.path.join(REPO, ".jax_cache")
    assert str(compile_cache.default_cache_dir()) == want
    assert compile_cache.enable_persistent_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # fixed: a second call (another process, another day) names the same
    # directory — nothing in it comes from a pid, a clock or a temp name
    assert compile_cache.enable_persistent_compile_cache() == want


def test_cache_helper_sets_nothing_on_the_cpu_platform(monkeypatch,
                                                       _cache_config):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_persistent_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
