"""Degrades that hide the device: kept on the CPU, refused on a TPU.

Deploy-time verification degrades to an in-process program when XLA:CPU's AOT
loader refuses a serialized executable, and a streamed boot degrades to the
fused program when the head sub-program cannot be fetched. On a TPU each of
these would let a broken device path serve and exit 0, so there they raise.
The backend check is ``compile_cache.on_tpu``, patched here; the programs
still compile and run on the CPU.
"""
import importlib

import pytest

from repro.core import compile_cache
from repro.core.artifact import FunctionSpec
from repro.core.compile_cache import CompileCache
from repro.core.snapshot import SnapshotStore

# the module: the package attribute ``repro.core.deploy`` is the function
deploy_mod = importlib.import_module("repro.core.deploy")

SPEC = FunctionSpec(arch="olmo-1b", batch_size=1, prompt_len=8, decode_steps=2)


def _refuse_loads(monkeypatch):
    def refuse(self, key):
        raise RuntimeError(f"AOT loader refused {key}")
    monkeypatch.setattr(CompileCache, "load_program", refuse)


def _deploy(tmp_path):
    return deploy_mod.deploy(SPEC, CompileCache(tmp_path / "images"),
                             SnapshotStore(tmp_path / "snaps"), str(tmp_path))


@pytest.fixture(scope="module")
def deployed(tmp_path_factory):
    return _deploy(tmp_path_factory.mktemp("deployed"))


@pytest.mark.parametrize("tpu", [False, True])
def test_serve_program_load_degrade(tmp_path, monkeypatch, tpu):
    monkeypatch.setattr(compile_cache, "on_tpu", lambda: tpu)
    _refuse_loads(monkeypatch)
    if tpu:
        with pytest.raises(RuntimeError, match="serve program failed on the TPU"):
            _deploy(tmp_path)
        return
    dep = _deploy(tmp_path)
    assert dep.fallback_program is not None
    assert dep.image.manifest.extra["aot_verified"] is False


@pytest.mark.parametrize("tpu", [False, True])
def test_split_degrade(tmp_path, monkeypatch, tpu):
    monkeypatch.setattr(compile_cache, "on_tpu", lambda: tpu)

    def broken_head(model, spec):
        raise RuntimeError("head sub-program does not build")
    monkeypatch.setattr(deploy_mod, "make_head_fn", broken_head)
    if tpu:
        with pytest.raises(RuntimeError, match="split failed on the TPU"):
            _deploy(tmp_path)
        return
    dep = _deploy(tmp_path)
    extra = dep.image.manifest.extra
    assert extra["aot_verified"] is True and extra["split_serve"] is False
    assert not dep.cache.has_split(dep.image.key)


@pytest.mark.parametrize("tpu", [False, True])
def test_bucket_and_decode_bundle_degrade(deployed, monkeypatch, tpu):
    monkeypatch.setattr(compile_cache, "on_tpu", lambda: tpu)
    monkeypatch.setattr(deployed, "_buckets", {})
    monkeypatch.setattr(deployed, "_decode_bundle", None)
    _refuse_loads(monkeypatch)
    if tpu:
        with pytest.raises(RuntimeError, match="bucket 2 failed on the TPU"):
            deployed.ensure_bucket(2)
        with pytest.raises(RuntimeError, match="decode bundle failed on the TPU"):
            deployed.ensure_decode(slots=2, page_size=4)
        return
    deployed.ensure_bucket(2)
    assert deployed._buckets[2] is not None            # in-process program kept
    assert deployed.ensure_decode(slots=2, page_size=4).aot_verified is False


@pytest.mark.parametrize("tpu", [False, True])
def test_streamed_boot_head_fetch_degrade(deployed, monkeypatch, tpu):
    from repro.core.drivers import UnikernelStreamDriver
    from repro.core.metrics import Timeline

    assert deployed.split_ok
    monkeypatch.setattr(compile_cache, "on_tpu", lambda: tpu)

    def lost_head():
        raise FileNotFoundError("head sub-program payload is gone")
    monkeypatch.setattr(deployed, "fetch_head_payload", lost_head)
    driver = UnikernelStreamDriver()
    if tpu:
        with pytest.raises(RuntimeError, match="head sub-program failed on the TPU"):
            driver.start(deployed, Timeline())
        return
    ex = driver.start(deployed, Timeline())             # fused program instead
    try:
        out = ex.run(deployed.example_tokens())
        assert out.shape == (SPEC.batch_size, SPEC.decode_steps)
    finally:
        ex.exit()
