"""chip_smoke.py driven tiny on the CPU mesh, plus the rules it stands
on: no chip, no result; one compile cache, placed from outside."""

import functools
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from mxnet_tpu import config  # noqa: E402


def test_train_phase_dp2_matches_one_device():
    out = chip_smoke.phase_train(
        model="resnet18_v1", classes=10, per_chip_batch=4, image=32,
        steps=3, devices=jax.devices()[:2])
    assert out["dp"] == 2 and out["global_batch"] == 8
    assert out["sharded_state_share"] > 0.5
    assert out["losses"][-1] < out["losses"][0]
    assert abs(out["losses"][0] - out["one_device_first_loss"]) < 0.05


def test_serve_phase_two_rungs():
    out = chip_smoke.phase_serve(
        model="resnet18_v1", classes=10, image=32, rungs=(1, 4),
        requests=8, threads=2)
    assert out["compiles"] == 2 and out["batches"] <= out["requests"]


def test_lm_phase_with_interpreted_flash(monkeypatch):
    # on the CPU the op takes the chunked XLA path; route it through the
    # Pallas kernels in interpret mode instead so the smoke's model runs
    # the code the chip compiles (no Mosaic call appears on this backend)
    from mxnet_tpu.ops import attention
    monkeypatch.setattr(
        attention, "flash_attention",
        functools.partial(attention.flash_attention, interpret=True))
    out = chip_smoke.phase_lm(
        vocab=64, dim=64, heads=4, layers=2, seq=64, per_chip_batch=2,
        steps=3, devices=jax.devices()[:2], kernels_per_layer=0,
        flash_shape=(1, 2, 64, 32))
    assert out["mosaic_calls"] == 0
    assert abs(out["losses"][0] - out["one_device_first_loss"]) < 0.05


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() == 1
    printed = capsys.readouterr().out.strip().splitlines()
    assert printed == ['chip_smoke: platform=cpu device_kind="cpu" '
                       'count=%d' % len(jax.devices())]


class TestCompileCacheRule:
    def test_environment_places_the_cache(self, tmp_path, monkeypatch):
        wanted = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", wanted)
        try:
            assert config.compile_cache_dir() == wanted
            assert config.enable_compile_cache() == wanted
            assert jax.config.jax_compilation_cache_dir == wanted
        finally:
            monkeypatch.undo()
            config.enable_compile_cache()

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        try:
            assert config.compile_cache_dir() == \
                os.path.join(REPO, ".jax_cache")
            assert config.enable_compile_cache() == \
                os.path.join(REPO, ".jax_cache")
        finally:
            monkeypatch.undo()
            config.enable_compile_cache()

    def test_nothing_in_the_package_sets_another(self):
        """The one ``jax_compilation_cache_dir`` update in the tree is
        ``enable_compile_cache``'s, and the old knob is gone."""
        hits = []
        for root, _, files in os.walk(os.path.join(REPO, "mxnet_tpu")):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(root, f)) as fh:
                        text = fh.read()
                    assert "MXNET_COMPILE_CACHE" not in text, f
                    if '"jax_compilation_cache_dir"' in text:
                        hits.append(f)
        assert hits == ["config.py"]

    def test_fleet_replicas_inherit_it(self, tmp_path, monkeypatch):
        from mxnet_tpu import serve
        wanted = str(tmp_path / "fleet")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", wanted)
        fleet = serve.Fleet([], replicas=1, workdir=str(tmp_path))
        try:
            assert fleet.compile_cache_dir == wanted
            env = fleet._replica_env()
            assert env["JAX_COMPILATION_CACHE_DIR"] == wanted
            assert "MXNET_COMPILE_CACHE_DIR" not in env
        finally:
            fleet.router.close()


class TestOneProcessPerChip:
    def test_fleet_larger_than_the_host_is_refused(self, tmp_path,
                                                   monkeypatch):
        from mxnet_tpu import serve
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
        with pytest.raises(serve.ServeError, match="needs 3 TPU chips"):
            serve.Fleet([], replicas=3, workdir=str(tmp_path))
        fleet = serve.Fleet([], replicas=2, workdir=str(tmp_path))
        try:
            envs = [fleet._replica_env(fleet._take_chip())
                    for _ in range(2)]
            assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
            assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
        finally:
            fleet.router.close()

    def test_launcher_refuses_more_workers_than_chips(self):
        import subprocess
        env = dict(os.environ, JAX_PLATFORMS="tpu,cpu",
                   TPU_VISIBLE_CHIPS="0")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "launch.py"),
             "-n", "2", "-s", "0", "--", sys.executable, "-c", "pass"],
            env=env, capture_output=True, text=True, timeout=60)
        assert r.returncode == 2
        assert "2 workers need 2 TPU chips" in r.stderr

    def test_cpu_pinned_host_has_no_chips_to_divide(self, monkeypatch):
        from mxnet_tpu import chips
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
        assert chips.host_chips() == []
