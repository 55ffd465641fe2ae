"""ZeRO-3 parameter offload (runtime/zero/param_offload.py).

The reference capability under test: training a model whose parameters do
not fit device memory by keeping them host-resident (CPU/NVMe) and
streaming one layer at a time (partition_parameters.py:701 remote_device +
partitioned_param_swapper.py:36). The budget assertion checks the device
never holds more than ~2 layers of a deep stack; the oracle assertion
checks the streamed training matches a monolithic pure-JAX Adam run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

import deepspeed_tpu
from deepspeed_tpu.runtime.zero.param_offload import Zero3OffloadEngine

HID = 64
NLAYERS = 8


class _Body(nn.Module):
    hidden: int = HID

    @nn.compact
    def __call__(self, x):
        return nn.relu(nn.Dense(self.hidden)(x))


class _Head(nn.Module):
    hidden: int = HID

    @nn.compact
    def __call__(self, x, batch):
        return jnp.mean((nn.Dense(self.hidden)(x) - batch[1]) ** 2)


def _layers():
    return [_Body() for _ in range(NLAYERS)] + [_Head()]


def _batch(seed=0, bs=16):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((bs, HID)).astype(np.float32),
            rng.standard_normal((bs, HID)).astype(np.float32))


def test_masters_are_c_contiguous_writable():
    """HostParamStore masters must be C-contiguous writable fp32 even
    when the backend hands back F-ordered or read-only arrays:
    np.array's default order='K' preserves an F layout, tripping the
    CPU-Adam kernel's _ptr contract (and zeros_like moments inherit the
    order). Regression for the gpt2-xl layered bench crash."""
    from deepspeed_tpu.runtime.zero.param_offload import HostParamStore
    st = HostParamStore()
    f_ordered = np.asfortranarray(
        np.arange(12, dtype=np.float32).reshape(3, 4))
    read_only = np.arange(4, dtype=np.float32)
    read_only.setflags(write=False)
    st.add_layer({"w": f_ordered, "b": read_only})
    for h in st.host_leaves(0):
        assert h.dtype == np.float32
        assert h.flags["C_CONTIGUOUS"], h.shape
        assert h.flags["WRITEABLE"]
        assert np.zeros_like(h).flags["C_CONTIGUOUS"]


def test_optimizer_offload_masters_writable():
    """Same contract for the optimizer-offload masters (zero/offload.py):
    an already-contiguous read-only full-slice leaf must still be copied
    into a writable master."""
    from deepspeed_tpu.runtime.zero.offload import OffloadedOptimizer
    ro = np.ones((4, 4), np.float32)
    ro.setflags(write=False)
    grads = {"w": np.ones((4, 4), np.float32)}
    off = OffloadedOptimizer(grads, lr=1e-3)
    off._init_masters(grads, {"w": ro})
    for shards in off.masters:
        for _, master in shards:
            assert master.flags["C_CONTIGUOUS"] and master.flags["WRITEABLE"]


def test_device_budget_and_training(tmp_path):
    eng = Zero3OffloadEngine(_layers(), _batch(), lr=1e-2, seed=0)
    losses = [float(eng.train_batch(_batch(s))) for s in range(8)]
    assert losses[-1] < losses[0]
    st = eng.store
    # device never held more than ~2 of the 9 layers simultaneously
    assert st.peak_live_bytes * 3 < st.total_param_bytes, (
        st.peak_live_bytes, st.total_param_bytes)
    assert st.live_bytes == 0  # everything released after the step


def test_matches_monolithic_adam_oracle():
    layers = _layers()
    eng = Zero3OffloadEngine(layers, _batch(), lr=1e-3, seed=3)

    # clone the engine's initial masters into a monolithic param list
    params0 = [
        jax.tree.unflatten(eng.store.treedefs[i],
                           [jnp.asarray(h) for h in eng.store.host_leaves(i)])
        for i in range(len(layers))
    ]

    def loss_fn(plist, batch):
        x = batch[0]
        for i, m in enumerate(layers[:-1]):
            x = m.apply({"params": plist[i]}, x)
        return layers[-1].apply({"params": plist[-1]}, x, batch)

    opt = optax.adam(1e-3)
    opt_state = opt.init(params0)
    params = params0
    oracle, streamed = [], []
    for s in range(5):
        b = _batch(s + 10)
        loss, g = jax.value_and_grad(loss_fn)(params, b)
        upd, opt_state = opt.update(g, opt_state)
        params = optax.apply_updates(params, upd)
        oracle.append(float(loss))
        streamed.append(float(eng.train_batch(b)))
    np.testing.assert_allclose(streamed, oracle, rtol=2e-4, atol=2e-5)


def test_nvme_mode_matches_ram_mode(tmp_path):
    ram = Zero3OffloadEngine(_layers(), _batch(), lr=1e-2, seed=1)
    nvme = Zero3OffloadEngine(_layers(), _batch(), lr=1e-2, seed=1,
                              nvme_path=str(tmp_path))
    for s in range(4):
        b = _batch(s + 20)
        lr_, ln_ = float(ram.train_batch(b)), float(nvme.train_batch(b))
        np.testing.assert_allclose(ln_, lr_, rtol=1e-6)


def test_checkpoint_roundtrip():
    eng = Zero3OffloadEngine(_layers(), _batch(), lr=1e-2, seed=2)
    for s in range(3):
        eng.train_batch(_batch(s))
    sd = eng.state_dict()
    cont = [float(eng.train_batch(_batch(s + 50))) for s in range(3)]

    fresh = Zero3OffloadEngine(_layers(), _batch(), lr=1e-2, seed=99)
    fresh.load_state_dict(sd)
    resumed = [float(fresh.train_batch(_batch(s + 50))) for s in range(3)]
    np.testing.assert_allclose(resumed, cont, rtol=1e-6)


def test_initialize_dispatches_offload_param(tmp_path):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_layers(),
        config={"train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {
                    "stage": 3,
                    "offload_param": {"device": "cpu"}}},
        sample_batch=_batch())
    assert isinstance(engine, Zero3OffloadEngine)
    l0 = float(engine.train_batch(_batch(1)))
    l1 = float(engine.train_batch(_batch(1)))
    assert l1 < l0


def test_initialize_offload_param_requires_layers():
    with pytest.raises(AssertionError, match="layered"):
        deepspeed_tpu.initialize(
            model=_Body(),
            config={"train_batch_size": 16,
                    "zero_optimization": {
                        "stage": 3, "offload_param": {"device": "cpu"}}},
            sample_batch=_batch())


def test_file_checkpoint_roundtrip(tmp_path):
    eng = Zero3OffloadEngine(_layers(), _batch(), lr=1e-2, seed=4)
    for s in range(3):
        eng.train_batch(_batch(s))
    eng.save_checkpoint(str(tmp_path), tag="t3",
                        client_state={"epoch": 1})
    assert (tmp_path / "latest").read_text() == "t3"
    cont = [float(eng.train_batch(_batch(s + 70))) for s in range(2)]

    fresh = Zero3OffloadEngine(_layers(), _batch(), lr=1e-2, seed=77)
    path, client = fresh.load_checkpoint(str(tmp_path))
    assert client == {"epoch": 1}
    resumed = [float(fresh.train_batch(_batch(s + 70))) for s in range(2)]
    np.testing.assert_allclose(resumed, cont, rtol=1e-6)


def test_offload_engine_rejects_unimplemented_config_keys():
    """ADVICE r2: config keys the layered engine does not implement must
    fail loudly, not silently change training behavior."""
    import flax.linen as nn
    import jax.numpy as jnp
    import pytest

    import deepspeed_tpu
    from deepspeed_tpu.runtime.config import DeepSpeedConfigError

    layers = [nn.Dense(8), lambda x, batch: jnp.mean((x - batch[1]) ** 2)]
    cfg = {
        "train_batch_size": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "offload_param": {"device": "cpu"}},
        "scheduler": {"type": "WarmupLR", "params": {}},
    }
    with pytest.raises(DeepSpeedConfigError, match="scheduler"):
        deepspeed_tpu.initialize(
            model=layers, config=cfg,
            sample_batch=(jnp.zeros((4, 8)), jnp.zeros((4, 8))))
