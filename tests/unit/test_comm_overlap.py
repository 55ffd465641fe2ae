"""PR-10 raw-speed units: bucketed gradient-collective overlap
(runtime/comm_overlap.py + the engine's shard_map variant) and the
whole-state one-sweep fused optimizer (ops/adam fused_adam_sweep + the
runtime/optim flatten shim).

Covers the ISSUE-10 satellite checklist: bucket assembly (size targets,
remainder bucket, single-leaf models, oversized leaves, dtype
boundaries), bucketed-pmean numerics vs per-leaf pmean, engine loss
parity overlap-on vs off (gas=1 fused AND gas>1 micro/apply) with the
HLO-census evidence that the per-leaf all-reduces collapsed to the
bucket count, the fallback envelope, and fused-sweep parity vs the
unfused optimizer at fp32/bf16/fp16-with-loss-scale including the
overflow-skip path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.simple import SimpleModel, sample_batch
from deepspeed_tpu.ops.adam.fused_adam import (adam_sweep_apply,
                                               fused_adam_sweep, sweep_pad)
from deepspeed_tpu.runtime import optim as optim_lib
from deepspeed_tpu.runtime.comm_overlap import (GradBucketSpec,
                                                build_grad_bucket_spec,
                                                bucketed_pmean,
                                                check_scheduler_flags,
                                                overlap_xla_flags)
from deepspeed_tpu.utils import groups

HIDDEN = 32


@pytest.fixture(autouse=True)
def _need8():
    if jax.device_count() < 8:
        pytest.skip("requires 8 devices")


# ------------------------------------------------------------ bucket spec
class TestBucketSpec:
    def _leaves(self, sizes, dtype=np.float32):
        return [np.zeros((s,), dtype) for s in sizes]

    def test_reverse_order_size_targets(self):
        # 10 leaves x 100 f32 = 400 B each; 1000 B target -> pairs,
        # assembled from the END of the tree (backward order)
        spec = build_grad_bucket_spec(self._leaves([100] * 10), 1000)
        assert spec.n_leaves == 10
        assert spec.buckets == ((9, 8), (7, 6), (5, 4), (3, 2), (1, 0))
        assert all(b == 800 for b in spec.bucket_bytes)

    def test_remainder_bucket(self):
        spec = build_grad_bucket_spec(self._leaves([100] * 5), 1000)
        assert spec.buckets == ((4, 3), (2, 1), (0,))
        assert spec.bucket_bytes[-1] == 400     # the remainder

    def test_single_leaf_model(self):
        spec = build_grad_bucket_spec(self._leaves([7]), 1 << 20)
        assert spec.buckets == ((0,),)
        assert spec.n_buckets == 1

    def test_oversized_leaf_gets_own_bucket(self):
        # leaf 1 is 4000 B against a 1000 B target: never split, never
        # packed with neighbours
        spec = build_grad_bucket_spec(self._leaves([50, 1000, 50]), 1000)
        assert (1,) in spec.buckets

    def test_mixed_dtypes_never_share_a_bucket(self):
        leaves = [np.zeros((10,), np.float32), np.zeros((10,), np.int32),
                  np.zeros((10,), np.float32)]
        spec = build_grad_bucket_spec(leaves, 1 << 20)
        for idxs in spec.buckets:
            kinds = {np.dtype(leaves[i].dtype).kind for i in idxs}
            assert len(kinds) == 1
        assert spec.n_buckets == 3      # f32 | i32 | f32 boundaries

    def test_empty_tree(self):
        assert build_grad_bucket_spec({}, 1000) == GradBucketSpec((), (), 0)

    def test_shape_dtype_structs_accepted(self):
        # abstract engines build the spec from ShapeDtypeStructs
        tree = {"a": jax.ShapeDtypeStruct((8, 8), jnp.float32),
                "b": jax.ShapeDtypeStruct((4,), jnp.float32)}
        spec = build_grad_bucket_spec(tree, 64)
        assert spec.n_leaves == 2 and spec.n_buckets == 2


# -------------------------------------------------------- bucketed pmean
class TestBucketedPmean:
    def test_matches_per_leaf_pmean(self):
        import functools

        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.utils.jax_compat import get_shard_map
        groups.initialize()
        mesh = groups.get_mesh()
        shard_map, kw = get_shard_map()
        rng = np.random.default_rng(0)
        data = {"a": rng.standard_normal((8, 2, 3)).astype(np.float32),
                "b": rng.standard_normal((8, 5)).astype(np.float32),
                "c": rng.standard_normal((8, 4)).astype(np.float32)}
        tmpl = jax.tree.map(lambda x: x[0], data)
        # 40-byte target, reverse packing: {c(16B)+b(20B)} share a bucket
        # (exercising the flatten/split offsets numerically) while a(24B)
        # overflows into a single-leaf bucket (the no-copy path)
        spec = build_grad_bucket_spec(tmpl, 40)
        assert spec.n_buckets == 2
        assert sorted(len(b) for b in spec.buckets) == [1, 2]

        def body(t):
            shard = jax.tree.map(lambda x: x[0], t)
            return bucketed_pmean(spec, shard, groups.DATA_AXIS)

        smap = functools.partial(shard_map, mesh=mesh)
        out = smap(body, in_specs=(P(groups.DATA_AXIS),),
                   out_specs=P(), **kw)(data)
        want = jax.tree.map(lambda x: x.mean(axis=0), data)
        for k in data:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(want[k]),
                                       rtol=1e-6, atol=1e-6)

    def test_single_leaf_bucket_reduces_fp32_keeps_dtype(self):
        # the singleton-bucket fast path honours the same fp32-reduction
        # invariant as the flattened path (spec counts float leaves at
        # 4 B/elem) and hands the leaf back in its own dtype
        import functools

        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.utils.jax_compat import get_shard_map
        groups.initialize()
        mesh = groups.get_mesh()
        shard_map, kw = get_shard_map()
        data = {"a": jnp.arange(8 * 6, dtype=jnp.bfloat16).reshape(8, 6)}
        tmpl = jax.tree.map(lambda x: x[0], data)
        spec = build_grad_bucket_spec(tmpl, 1)  # forces its own bucket
        assert spec.buckets == ((0,),)
        assert spec.bucket_bytes == (6 * 4,)   # fp32 accounting

        def body(t):
            shard = jax.tree.map(lambda x: x[0], t)
            return bucketed_pmean(spec, shard, groups.DATA_AXIS)

        smap = functools.partial(shard_map, mesh=mesh)
        out = smap(body, in_specs=(P(groups.DATA_AXIS),),
                   out_specs=P(), **kw)(data)
        assert out["a"].dtype == jnp.bfloat16
        want = np.asarray(data["a"], dtype=np.float32).mean(axis=0)
        np.testing.assert_allclose(
            np.asarray(out["a"], dtype=np.float32), want,
            rtol=8e-3, atol=1e-6)  # bf16 storage tolerance

    def test_spec_tree_mismatch_raises(self):
        spec = build_grad_bucket_spec([np.zeros(3)], 100)
        with pytest.raises(AssertionError, match="diverged"):
            bucketed_pmean(spec, [jnp.zeros(3), jnp.zeros(3)], "data")


# --------------------------------------------------------- xla flag helper
class TestSchedulerFlags:
    def test_tpu_flags_nonempty_cpu_empty(self):
        assert overlap_xla_flags("tpu")
        assert overlap_xla_flags("cpu") == ()
        assert check_scheduler_flags("cpu") is True

    def test_check_reads_env(self, monkeypatch):
        monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
        assert check_scheduler_flags("tpu") is False

    @pytest.mark.parametrize("spell", ["", "=true", "=1", "=True", "=yes"])
    def test_check_accepts_truthy_spellings(self, monkeypatch, spell):
        # absl accepts bare --flag / =true / =1 / =yes as true; a
        # correctly-armed launch in any spelling must not be reported
        # as mis-armed
        from deepspeed_tpu.runtime.comm_overlap import overlap_xla_flags
        flags = " ".join(f.partition("=")[0] + spell
                         for f in overlap_xla_flags("tpu"))
        monkeypatch.setenv("XLA_FLAGS", flags)
        assert check_scheduler_flags("tpu") is True

    @pytest.mark.parametrize("spell", ["=false", "=0", "=False"])
    def test_check_rejects_falsy_spellings(self, monkeypatch, spell):
        from deepspeed_tpu.runtime.comm_overlap import overlap_xla_flags
        flags = []
        for i, f in enumerate(overlap_xla_flags("tpu")):
            flags.append(f.partition("=")[0] + (spell if i == 0 else "=true"))
        monkeypatch.setenv("XLA_FLAGS", " ".join(flags))
        assert check_scheduler_flags("tpu") is False
        monkeypatch.setenv(
            "XLA_FLAGS", " ".join(overlap_xla_flags("tpu")))
        assert check_scheduler_flags("tpu") is True


# ------------------------------------------------------------ flatten shim
class TestFlattenShim:
    def test_roundtrip_with_padding_and_dtypes(self):
        tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": jnp.ones((5,), jnp.bfloat16)}
        vec, spec = optim_lib.flatten_tree(tree, pad_to=16)
        assert vec.shape == (16,) and vec.dtype == jnp.float32
        assert spec.n == 11 and spec.n_pad == 16
        back = optim_lib.unflatten_tree(vec, spec)
        assert back["b"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.asarray(tree["w"]))

    def test_wrong_length_raises(self):
        vec, spec = optim_lib.flatten_tree({"a": jnp.zeros(3)}, pad_to=4)
        with pytest.raises(AssertionError):
            optim_lib.unflatten_tree(jnp.zeros(8), spec)


# ----------------------------------------------------------- sweep kernel
class TestSweepKernel:
    def _bufs(self, seed=0):
        n = sweep_pad()
        rng = np.random.default_rng(seed)
        p, g, m = (jnp.asarray(rng.standard_normal(n), jnp.float32)
                   for _ in range(3))
        v = jnp.abs(jnp.asarray(rng.standard_normal(n), jnp.float32))
        return p, g, m, v

    @pytest.mark.parametrize("cast", [None, jnp.bfloat16])
    def test_pallas_matches_jnp_chain(self, cast):
        p, g, m, v = self._bufs()
        kw = dict(b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.01,
                  adam_w_mode=True, cast_dtype=cast)
        a = adam_sweep_apply(p, g, m, v, 1e-3, 0.9, 0.99, 0.5,
                             use_pallas=True, **kw)
        b = adam_sweep_apply(p, g, m, v, 1e-3, 0.9, 0.99, 0.5,
                             use_pallas=False, **kw)
        for x, y in zip(a, b):
            if x is None:
                assert y is None
                continue
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32),
                                       rtol=1e-6, atol=1e-7)

    def test_cast_output_is_updated_param(self):
        p, g, m, v = self._bufs(1)
        u, _, _, cast = adam_sweep_apply(
            p, g, m, v, 1e-3, 0.9, 0.99, 1.0, cast_dtype=jnp.bfloat16,
            use_pallas=False)
        np.testing.assert_allclose(
            np.asarray(cast, np.float32),
            np.asarray((p + u).astype(jnp.bfloat16), np.float32))

    def test_clip_coef_scales_like_pre_clipped_grads(self):
        p, g, m, v = self._bufs(2)
        a = adam_sweep_apply(p, g, m, v, 1e-3, 0.9, 0.99, 0.25,
                             use_pallas=False)
        b = adam_sweep_apply(p, g * 0.25, m, v, 1e-3, 0.9, 0.99, 1.0,
                             use_pallas=False)
        for x, y in zip(a[:3], b[:3]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-7)


# -------------------------------------------------------- sweep optimizer
class TestSweepOptimizer:
    def _tree(self, seed=0):
        rng = np.random.default_rng(seed)
        return {"dense": {"kernel": jnp.asarray(
                    rng.standard_normal((16, 8)), jnp.float32),
                "bias": jnp.asarray(rng.standard_normal(8), jnp.float32)},
                "out": jnp.asarray(rng.standard_normal((8,)), jnp.float32)}

    def test_matches_unfused_adam(self):
        params = self._tree(0)
        grads = self._tree(1)
        kw = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
        ref = optim_lib.adam(**kw)
        swp = fused_adam_sweep(**kw)
        rs, ss = ref.init(params), swp.init(params)
        assert swp.fuses_clip and not ref.fuses_clip
        for step in range(3):
            ru, rs = ref.update(grads, rs, params, 1e-3)
            su, ss = swp.update(grads, ss, params, 1e-3)
            for a, b in zip(jax.tree.leaves(ru), jax.tree.leaves(su)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-7)
        assert ss.mu.ndim == 1      # whole-state flat moments
        assert ss.mu.size % sweep_pad() == 0

    def test_clip_coef_matches_clip_then_update(self):
        params, grads = self._tree(0), self._tree(1)
        clipped, _ = optim_lib.clip_by_global_norm(grads, 0.1)
        norm = optim_lib.global_norm(grads)
        cc = jnp.minimum(0.1 / (norm + 1e-6), 1.0)
        swp = fused_adam_sweep()
        s = swp.init(params)
        u1, _ = swp.update(grads, s, params, 1e-3, clip_coef=cc)
        u2, _ = swp.update(clipped, s, params, 1e-3)
        for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


# ------------------------------------------------------------- engine e2e
def _engine(hidden=HIDDEN, nlayers=4, seed=42, **over):
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }
    cfg.update(over)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=hidden, nlayers=nlayers), config=cfg,
        sample_batch=sample_batch(2, hidden), seed=seed)
    return engine


def _batches(n, hidden=HIDDEN, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((16, hidden)).astype(np.float32),
             rng.standard_normal((16, hidden)).astype(np.float32))
            for _ in range(n)]


def _run(engine, batches):
    out = [float(jax.device_get(engine.train_batch(batch=b)))
           for b in batches]
    engine.close()
    return out


class TestEngineOverlap:
    def test_loss_parity_and_census_collapse(self):
        """Overlap on matches off to float tolerance AND the arrays the
        compiled program all-reduces collapse from one-per-leaf to
        one-per-bucket (+1 loss pmean) — the PR-2 census is the
        structural evidence the ISSUE acceptance names. The unit is the
        reduced ARRAY, not the instruction: XLA's all-reduce combiner
        (jax 0.9) folds either program's reductions into one tuple
        all-reduce, whose result tuple still lists every payload."""
        batches = _batches(4)
        tel = {"enabled": True, "trace": False, "jsonl": False,
               "prometheus": False, "cost_explorer": {"enabled": True}}

        def reduced_arrays(engine):
            return sum(len(op.shapes)
                       for op in engine.get_cost_census().collectives
                       if op.kind == "all-reduce")

        eng_off = _engine(telemetry=tel)
        losses_off = [float(jax.device_get(eng_off.train_batch(batch=b)))
                      for b in batches]
        off_ar = reduced_arrays(eng_off)
        n_leaves = len(jax.tree.leaves(eng_off.state.params))
        eng_off.close()

        eng_on = _engine(telemetry=tel,
                         comm_overlap={"enabled": True,
                                       "bucket_mb": 0.005})
        assert eng_on._comm_overlap_on
        n_buckets = eng_on._overlap_spec.n_buckets
        assert 1 < n_buckets < eng_on._overlap_spec.n_leaves
        losses_on = [float(jax.device_get(eng_on.train_batch(batch=b)))
                     for b in batches]
        on_ar = reduced_arrays(eng_on)
        eng_on.close()

        np.testing.assert_allclose(losses_on, losses_off,
                                   rtol=1e-4, atol=1e-5)
        assert off_ar >= n_leaves, (off_ar, n_leaves)
        assert on_ar < off_ar, (on_ar, off_ar)
        assert on_ar <= n_buckets + 2, (on_ar, n_buckets)

    def test_gas_micro_apply_parity(self):
        """The gas>1 micro/apply split rides the same bucketed vg."""
        batches = _batches(3)
        gas_cfg = dict(train_batch_size=16,
                       train_micro_batch_size_per_gpu=1,
                       gradient_accumulation_steps=2)
        l_off = _run(_engine(**gas_cfg), batches)
        eng = _engine(**gas_cfg, comm_overlap={"enabled": True,
                                               "bucket_mb": 0.005})
        assert eng._comm_overlap_on and eng._jit_train is None
        l_on = _run(eng, batches)
        np.testing.assert_allclose(l_on, l_off, rtol=1e-4, atol=1e-5)

    def test_zero2_falls_back_with_one_warning(self, monkeypatch):
        from deepspeed_tpu.runtime import engine as engine_mod
        warns = []
        monkeypatch.setattr(engine_mod.logger, "warning",
                            lambda msg, *a, **k: warns.append(str(msg)))
        eng = _engine(zero_optimization={"stage": 2},
                      comm_overlap={"enabled": True})
        assert not eng._comm_overlap_on
        assert sum("comm_overlap" in w and "falls back" in w
                   for w in warns) == 1
        eng.close()

    def test_broadcast_leaf_rejected(self):
        eng = _engine(comm_overlap={"enabled": True, "bucket_mb": 1})
        assert eng._comm_overlap_on
        with pytest.raises(NotImplementedError, match="comm_overlap"):
            eng.train_batch(batch=(
                np.zeros((16, HIDDEN), np.float32),
                np.zeros((1, HIDDEN), np.float32)))
        eng.close()

    def test_clipping_parity_under_overlap(self):
        batches = _batches(3)
        l_off = _run(_engine(gradient_clipping=0.05), batches)
        l_on = _run(_engine(gradient_clipping=0.05,
                            comm_overlap={"enabled": True,
                                          "bucket_mb": 0.005}), batches)
        np.testing.assert_allclose(l_on, l_off, rtol=1e-4, atol=1e-5)


class TestEngineSweep:
    """Fused-sweep parity vs the unfused optimizer through the REAL
    engine step — the satellite's fp32/bf16/fp16-with-loss-scale matrix
    plus the overflow-skip path."""

    def _cfg(self, sweep, prec):
        over = {"optimizer": {"type": "Adam",
                              "params": {"lr": 1e-2, "weight_decay": 0.01,
                                         "sweep": sweep}},
                "gradient_clipping": 0.1}
        if prec == "bf16":
            over["bf16"] = {"enabled": True}
        if prec == "fp16":
            over["fp16"] = {"enabled": True, "loss_scale": 0,
                            "initial_scale_power": 8}
        return over

    @pytest.mark.parametrize("prec", ["fp32", "bf16", "fp16"])
    def test_loss_parity(self, prec):
        batches = _batches(4)
        l_ref = _run(_engine(**self._cfg(False, prec)), batches)
        eng = _engine(**self._cfg(True, prec))
        assert getattr(eng.optimizer, "fuses_clip", False)
        l_swp = _run(eng, batches)
        # documented ULP bound: the flatten changes fusion associativity,
        # so fp16 trajectories agree to float tolerance, not bitwise
        np.testing.assert_allclose(l_swp, l_ref, rtol=2e-4, atol=1e-5)

    def test_fp16_overflow_skip_parity(self):
        """A poisoned batch must skip the step IDENTICALLY under the
        sweep: same skipped_steps, same loss-scale trajectory, same
        params afterwards (the lax.cond skip path bypasses the sweep)."""
        bad = (np.full((16, HIDDEN), 1e38, np.float32),
               np.zeros((16, HIDDEN), np.float32))
        good = _batches(2, seed=3)

        def run(sweep):
            eng = _engine(**self._cfg(sweep, "fp16"))
            scale0 = eng.loss_scale
            eng.train_batch(batch=bad)
            eng.train_batch(batch=bad)
            skipped, scale = eng.skipped_steps, eng.loss_scale
            losses = [float(jax.device_get(eng.train_batch(batch=b)))
                      for b in good]
            leaf = np.asarray(
                jax.device_get(jax.tree.leaves(eng.state.params)[0]))
            step = int(jax.device_get(eng.state.step))
            eng.close()
            return scale0, skipped, scale, losses, leaf, step

        ref, swp = run(False), run(True)
        assert ref[0] == swp[0]
        assert ref[1] == swp[1] == 2            # both bad steps skipped
        assert ref[2] == swp[2] == ref[0] / 2   # hysteresis exhausted once
        assert ref[5] == swp[5] == 2            # applied steps only
        np.testing.assert_allclose(swp[3], ref[3], rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(swp[4], ref[4], rtol=2e-4, atol=1e-6)

    def test_sweep_rejected_for_non_adam(self):
        with pytest.raises(ValueError, match="sweep"):
            _engine(optimizer={"type": "Lamb",
                               "params": {"lr": 1e-3, "sweep": True}})

    def test_sweep_composes_with_comm_overlap(self):
        batches = _batches(3)
        l_ref = _run(_engine(**self._cfg(False, "fp32")), batches)
        l_both = _run(_engine(**self._cfg(True, "fp32"),
                              comm_overlap={"enabled": True,
                                            "bucket_mb": 0.005}), batches)
        np.testing.assert_allclose(l_both, l_ref, rtol=1e-4, atol=1e-5)
