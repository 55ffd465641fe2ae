"""CPU pins for the bring-up contract (PR 21): ``chip_smoke.py`` at the toy
size, the no-fallback rules of the on-chip entry points, the compile-cache
placement, one process per chip, and the kernels the TPU compiler refused
before the repair (cross-lowered here — Mosaic itself only runs on the
chip, see ``chip_smoke.py --kernels``)."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from deepspeed_tpu.utils import chip  # noqa: E402


def _run(args, tmp_path, devices=1, timeout=600):
    """Run a repo-root script in a fresh interpreter on the CPU backend,
    with the compile cache placed outside the checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------------ chip_smoke.py
class TestChipSmokeScript:
    def test_tiny_mode_runs_both_phases(self, tmp_path):
        """--tiny on four virtual devices: the four-chip host's layouts
        (one device, dp=4 ZeRO-1, dp=2 x mp=2 ZeRO-3) and the serve phase
        all pass, and the last stdout line is the contract's object."""
        in_checkout = os.path.join(ROOT, ".jax_compilation_cache")
        had_one = os.path.exists(in_checkout)
        p = _run(["chip_smoke.py", "--tiny"], tmp_path, devices=4)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
        lines = p.stdout.strip().splitlines()
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
        summary = json.loads(lines[-2])
        assert list(summary)[-1] == "claim" and summary["claim"] is None
        assert [t["layout"] for t in summary["train"]] == [
            "dp=1 mp=1 zero=1", "dp=4 mp=1 zero=1", "dp=2 mp=2 zero=3"]
        for t in summary["train"]:
            assert t["losses"][-1] < t["losses"][0]
        assert "flash_vs_reference" in summary["train"][0]
        assert max(summary["train"][2]["params_share_per_device"]
                   .values()) <= 0.35
        serve = summary["serve"]
        assert serve["requests"] == 8
        assert serve["compile_stats"] == {
            "decode_signatures": 1, "prefill_signatures": 1, "retraces": 0}
        # the cache went where the variable said, not into the checkout
        assert summary["compile_cache_dir"] == str(tmp_path / "cc")
        assert any((tmp_path / "cc").iterdir())
        assert os.path.exists(in_checkout) == had_one

    def test_no_chip_without_tiny_exits_nonzero(self, tmp_path):
        p = _run(["chip_smoke.py"], tmp_path)
        assert p.returncode != 0
        assert '"ok"' not in p.stdout and "no accelerator" in p.stderr

    def test_kernels_mode_needs_the_chip(self, monkeypatch, capsys):
        import chip_smoke
        monkeypatch.setattr(chip, "enable_compile_cache", lambda: "unused")
        with pytest.raises(SystemExit) as e:
            chip_smoke.main(["--kernels"])
        assert e.value.code not in (0, None)
        assert '"ok"' not in capsys.readouterr().out


# ------------------------------------------------------------------ bench.py
class TestBenchRefusals:
    def _main(self, monkeypatch):
        import bench
        monkeypatch.setattr(chip, "enable_compile_cache", lambda: "unused")
        return bench.main

    def test_fails_without_a_tpu(self, monkeypatch, capsys):
        with pytest.raises(SystemExit) as e:
            self._main(monkeypatch)()
        assert "no accelerator" in str(e.value.code)
        assert capsys.readouterr().out == ""

    def test_unknown_device_kind_is_an_error_not_a_default_peak(
            self, monkeypatch, capsys):
        main = self._main(monkeypatch)
        monkeypatch.setattr(chip, "require_accelerator", lambda: {
            "platform": "tpu", "kind": "TPU v99", "count": 1})
        with pytest.raises(SystemExit) as e:
            main()
        assert "TPU v99" in str(e.value.code)
        assert "KNOWN_CHIPS" in str(e.value.code)
        assert capsys.readouterr().out == ""


# ------------------------------------------------------------- compile cache
class TestCompileCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them: the
        helper must not re-point the cache of the whole pytest process."""
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_var_set_sets_nothing_in_code(self, monkeypatch, updates):
        monkeypatch.setenv(chip.CACHE_ENV_VAR, "/some/dir")
        assert chip.enable_compile_cache() == "/some/dir"
        assert updates == {}

    def test_unset_uses_the_fixed_checkout_path(self, monkeypatch, updates):
        monkeypatch.delenv(chip.CACHE_ENV_VAR, raising=False)
        want = os.path.join(ROOT, ".jax_compilation_cache")
        assert chip.enable_compile_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
        # same path on every call: it is part of the cache key
        assert chip.enable_compile_cache() == want


# ------------------------------------------------------ one process per chip
class TestOneProcessPerChip:
    def test_imports_initialise_no_backend(self, tmp_path):
        """A parent that only IMPORTS the package, the launcher and the
        server must not take the chip from the children it starts."""
        p = _run(["-c", (
            "import deepspeed_tpu, deepspeed_tpu.launcher.runner, "
            "deepspeed_tpu.serving, deepspeed_tpu.utils.chip as chip\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "assert not chip.holds_accelerator()\n"
            "assert not xla_bridge._backends\n")], tmp_path)
        assert p.returncode == 0, p.stderr[-3000:]

    def test_holds_accelerator_reads_the_live_backend(self, monkeypatch):
        jax.devices()                       # the test process is on the CPU
        assert not chip.holds_accelerator()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert chip.holds_accelerator()

    def test_require_accelerator_refuses_the_cpu(self):
        with pytest.raises(SystemExit) as e:
            chip.require_accelerator()
        assert "does not fall back" in str(e.value.code)

    def test_scheduler_refuses_local_children_of_a_chip_holder(
            self, monkeypatch, tmp_path):
        from deepspeed_tpu.autotuning.scheduler import ResourceManager
        monkeypatch.setattr(chip, "holds_accelerator", lambda: True)
        rm = ResourceManager(cmd_template=[sys.executable, "-c", "pass"],
                             exps_dir=str(tmp_path))
        rm.schedule_experiments([{"train_batch_size": 8}])
        with pytest.raises(RuntimeError, match="holds its chips"):
            rm.run()
        # in-process experiments need no second process: still allowed
        rm = ResourceManager(run_fn=lambda cfg: 1.0)
        rm.schedule_experiments([{"train_batch_size": 8}])
        assert rm.run()[0].metric == 1.0

    def test_local_launcher_pins_one_chip_per_child(self):
        from deepspeed_tpu.launcher.runner import local_chip_pinning
        envs = [local_chip_pinning(i, 4) for i in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        for i, e in enumerate(envs):
            assert e["TPU_PROCESS_ADDRESSES"].split(",")[i].endswith(
                e["TPU_PROCESS_PORT"])
        assert local_chip_pinning(1, 2)["TPU_PROCESS_BOUNDS"] == "2,1,1"

    def test_launcher_stops_peers_when_one_child_fails(self):
        """A worker that dies must not leave its peers blocked in a
        rendezvous (never hang): wait_all terminates them."""
        from deepspeed_tpu.launcher.runner import wait_all
        sleeper = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)"])
        failer = subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"])
        t0 = time.monotonic()
        try:
            assert wait_all([sleeper, failer]) == 3
            assert time.monotonic() - t0 < 60
            assert sleeper.poll() is not None
        finally:
            if sleeper.poll() is None:
                sleeper.kill()
        ok = [subprocess.Popen([sys.executable, "-c", "pass"])
              for _ in range(2)]
        assert wait_all(ok) == 0


# ------------------------------------------------- no fallback on the device
def test_attention_raises_when_flash_cannot_import_on_tpu(monkeypatch):
    """On a TPU the flash kernel is THE path: if its module fails to
    import, attention() raises — it does not drop to O(S^2) XLA."""
    import deepspeed_tpu.ops.transformer as transformer_pkg
    from deepspeed_tpu.ops import _platform
    from deepspeed_tpu.ops.transformer.attention import attention
    monkeypatch.setattr(_platform, "effective_platform", lambda: "tpu")
    monkeypatch.delattr(transformer_pkg, "flash", raising=False)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.ops.transformer.flash",
                        None)
    q = jnp.zeros((1, 2, 512, 64), jnp.bfloat16)
    with pytest.raises(ImportError):
        attention(q, q, q)
    # below the flash crossover the dispatcher picks XLA by design, on
    # any platform — that choice is not a fallback
    short = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)
    assert attention(short, short, short).shape == short.shape


# ---------------------------------- kernels the TPU compiler used to refuse
def _lowers_for_tpu(fn, *avals):
    """Cross-lower ``fn`` for the TPU platform from the CPU: runs the
    Pallas -> Mosaic lowering (block-shape and cast checks) without a
    chip. Returns the number of tpu_custom_call sites."""
    from jax import export
    exp = export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    return exp.mlir_module().count("tpu_custom_call")


@pytest.mark.parametrize("mp_size", [1, 2], ids=["dp8", "dp4_mp2"])
def test_flash_lowers_under_a_multi_device_mesh(monkeypatch, mp_size):
    """GSPMD cannot partition a Mosaic kernel: on jax 0.9 a bare
    pallas_call under a sharded jit raises (every dp>1 / mp>1 layout on
    the four-chip host did). attention() shard_maps the flash kernel
    over the mesh — batch over data, heads over model."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.transformer import flash
    from deepspeed_tpu.ops.transformer.attention import attention
    from deepspeed_tpu.utils import groups
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    mesh = groups.initialize(mp_size=mp_size)
    spec = P("data", "model" if mp_size > 1 else None, None, None)
    q = jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))

    def loss(fn):
        return jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32)), (0, 1, 2))

    with pytest.raises(NotImplementedError, match="shard_map"):
        _lowers_for_tpu(loss(lambda q, k, v: flash.flash_attention(
            q, k, v, True, None)), q, q, q)
    assert _lowers_for_tpu(loss(lambda q, k, v: attention(
        q, k, v, use_flash=True)), q, q, q) == 2     # fwd; dkv writes dq


@pytest.mark.parametrize("lens_shape", [(), (8,)], ids=["scalar", "per_seq"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_lowers_for_tpu(monkeypatch, lens_shape, int8):
    """The decode kernel's cache length rides scalar prefetch: a rank-1
    SMEM block of one element is refused by the TPU lowering."""
    from deepspeed_tpu.ops.transformer import decode
    monkeypatch.setattr(decode, "_interpret", lambda: False)
    B, H, T, D = 8, 16, 1024, 64
    q = jax.ShapeDtypeStruct((B, H, 1, D), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct(lens_shape, jnp.int32)
    if int8:
        kv = jax.ShapeDtypeStruct((B, H, T, D), jnp.int8)
        sc = jax.ShapeDtypeStruct((B, H, T), jnp.float32)
        n = _lowers_for_tpu(
            lambda q, k, ks, v, vs, n: decode.decode_attention_quantized(
                q, k, ks, v, vs, n, use_flash=True), q, kv, sc, kv, sc, lens)
    else:
        kv = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)
        n = _lowers_for_tpu(
            lambda q, k, v, n: decode.decode_attention(
                q, k, v, n, use_flash=True), q, kv, kv, lens)
    assert n >= 1


@pytest.mark.parametrize("stochastic", [False, True])
def test_quantizer_kernel_lowers_for_tpu(monkeypatch, stochastic):
    from deepspeed_tpu.ops.quantizer import quantizer
    monkeypatch.setattr(quantizer, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((1024, 4096), jnp.float32)
    assert _lowers_for_tpu(
        lambda x: quantizer.quantize(x, num_bits=8, groups=1024,
                                     stochastic=stochastic, seed=7), x) >= 1
