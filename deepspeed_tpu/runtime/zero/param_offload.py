"""ZeRO-3 parameter offload: params resident on host (CPU RAM or NVMe),
streamed to the device layer-by-layer.

TPU-native rebuild of the reference's "40B params on one 32GB GPU"
machinery: ``zero.Init`` remote_device cpu/nvme
(deepspeed/runtime/zero/partition_parameters.py:701), the fetch/release
``PartitionedParameterCoordinator`` (zero/stage3.py:172), and the
``AsyncPartitionedParameterSwapper`` (swap_tensor/partitioned_param_swapper
.py:36). The reference intercepts nn.Module construction and autograd with
hooks because PyTorch is eager; under XLA the equivalent is a host-driven
layer loop:

* the model is a SEQUENCE of flax layers (the LayerSpec decomposition the
  reference's pipeline module also uses) — the full parameter set NEVER
  exists on the device;
* ``zero_init`` materialises each layer's params once, pulls them to host
  fp32 masters, and frees the device copy (zero.Init semantics: peak
  device residency = one layer);
* forward fetches layer i's params (async ``device_put`` = the allgather
  of ``fetch_sub_module``), prefetches layer i+1 (double buffering —
  ``__prefetch_nvme_param_partitions`` stage3.py:470), computes, releases;
* backward re-fetches each layer and recomputes its VJP locally (layer-
  granular rematerialisation — the PyTorch build re-fetches params via
  PreBackwardFunction hooks, stage3.py:496); gradients stream straight to
  host fp32 buffers;
* the optimizer step is a host CPU-Adam sweep (csrc/cpu_adam.cpp via
  ops/adam/cpu_adam.py) over the masters, per layer, so NVMe-resident
  masters only visit RAM one layer at a time.

Scope: single-device data path (the point is fitting a model that exceeds
one chip's HBM); compose dp/tp via the main engine when the model fits.
"""

import os
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.logging import log_dist


def _nbytes(tree) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


_store_ids = iter(range(1 << 30))


class HostParamStore:
    """Per-layer host fp32 masters with optional NVMe backing and live-
    bytes accounting (the swap half of partitioned_param_swapper.py:36)."""

    def __init__(self, nvme_path: Optional[str] = None,
                 swap_folder: Optional[str] = None):
        # swap keys are namespaced per store so several stores may share
        # one caller-supplied folder without clobbering each other
        self._key_prefix = f"st{next(_store_ids)}_{os.getpid()}_"
        self._ram: List[Optional[List[np.ndarray]]] = []
        self.treedefs: List[Any] = []
        self.swapper = None
        self._swap_folder = None
        self._owns_folder = False
        if nvme_path is not None:
            from deepspeed_tpu.runtime.swap_tensor.swapper import \
                AsyncTensorSwapper
            self._owns_folder = swap_folder is None
            self._swap_folder = swap_folder or os.path.join(
                nvme_path, f"ds_param_offload_{os.getpid()}")
            self.swapper = AsyncTensorSwapper(self._swap_folder)
        # device residency accounting (tests assert peak << total)
        self.live_bytes = 0
        self.peak_live_bytes = 0
        self.total_param_bytes = 0
        self._dev: dict = {}
        self._dev_bytes: dict = {}

    def _key(self, i: int, j: int) -> str:
        return f"{self._key_prefix}L{i}_p{j}"

    # ------------------------------------------------------------- host side
    def add_layer(self, params) -> int:
        """Take ownership of one layer's params as host fp32 leaves."""
        leaves, treedef = jax.tree.flatten(params)
        # np.array (not asarray): device_get returns read-only views, and
        # these buffers are the in-place-updated fp32 masters. order="C"
        # is load-bearing: a backend may hand back F-ordered arrays, and
        # the default order="K" would preserve that — masters
        # and their zeros_like moments must honor the CPU-Adam kernel's
        # C-contiguity contract
        host = [np.array(jax.device_get(l), np.float32, order="C")
                for l in leaves]
        self.total_param_bytes += sum(h.nbytes for h in host)
        i = len(self.treedefs)
        self.treedefs.append(treedef)
        if self.swapper is not None:
            for j, h in enumerate(host):
                self.swapper.swap_out(self._key(i, j), h)
            self.swapper.synchronize()
            self._ram.append(None)
        else:
            self._ram.append(host)
        return i

    def host_leaves(self, i: int) -> List[np.ndarray]:
        """Masters of layer i in RAM (swapped in from NVMe if backed)."""
        if self._ram[i] is not None:
            return self._ram[i]
        return [self.swapper.swap_in(self._key(i, j))
                for j in range(self.treedefs[i].num_leaves)]

    def write_back(self, i: int, leaves: List[np.ndarray]):
        """Persist updated masters (NVMe mode; RAM mode updates in place)."""
        if self._ram[i] is not None:
            return
        for j, h in enumerate(leaves):
            self.swapper.swap_out(self._key(i, j), h)
        self.swapper.synchronize()

    def close(self):
        """Delete this run's NVMe swap files (masters are full model size —
        leaking them across runs fills the device). A caller-supplied
        swap_folder may be shared, so only this store's own files go."""
        if self.swapper is None or self._swap_folder is None:
            return
        self.swapper.synchronize()
        if self._owns_folder:
            import shutil
            shutil.rmtree(self._swap_folder, ignore_errors=True)
        else:
            for i, td in enumerate(self.treedefs):
                for j in range(td.num_leaves):
                    try:
                        os.remove(self.swapper._path(self._key(i, j)))
                    except OSError:
                        pass
        self.swapper = None

    def __del__(self):  # pragma: no cover — best-effort cleanup
        try:
            self.close()
        except Exception:
            pass

    # ----------------------------------------------------------- device side
    def fetch(self, i: int, dtype) -> Any:
        """Async put of layer i's params to device (fetch_sub_module)."""
        if i in self._dev:
            return self._dev[i]
        leaves = [jnp.asarray(h, dtype) for h in self.host_leaves(i)]
        tree = jax.tree.unflatten(self.treedefs[i], leaves)
        self._dev[i] = tree
        self._dev_bytes[i] = _nbytes(tree)
        self.live_bytes += self._dev_bytes[i]
        self.peak_live_bytes = max(self.peak_live_bytes, self.live_bytes)
        return tree

    def release(self, i: int):
        """Drop the device copy (release_sub_module / param.partition())."""
        if i in self._dev:
            self.live_bytes -= self._dev_bytes.pop(i)
            del self._dev[i]


class Zero3OffloadEngine:
    """Train a layered model whose parameters exceed device memory.

    ``layers[:-1]`` map ``x -> x``; ``layers[-1]`` maps ``(x, batch) ->
    scalar loss`` (the LayerSpec + loss-head decomposition). ``input_fn``
    extracts the first layer's input from a batch (default ``batch[0]``).
    """

    def __init__(self, layers: Sequence, sample_batch, lr=1e-3,
                 betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                 adamw_mode=True, compute_dtype=jnp.float32,
                 input_fn: Callable = None, nvme_path: Optional[str] = None,
                 seed: int = 0):
        self.layers = list(layers)
        assert len(self.layers) >= 2, "need at least one body layer + loss head"
        self.input_fn = input_fn or (lambda b: b[0])
        self.compute_dtype = compute_dtype
        self.lr = lr
        self._betas, self._eps, self._wd = betas, eps, weight_decay
        self._adamw = adamw_mode
        self.store = HostParamStore(nvme_path=nvme_path)
        self._adam = _HostAdam(betas, eps, weight_decay, adamw_mode)
        self.global_steps = 0

        # per-layer compiled fns: init, fwd, vjp-recompute, loss head
        # grad. Deduped by module equality: a 48-block GPT stack compiles
        # ONE init + ONE fwd + ONE bwd program shared by every identical
        # block instead of 144 (flax modules are value-hashable
        # dataclasses). Jitting init/apply keeps a 1.5B-param zero_init
        # from dispatching every primitive as its own program.
        init_cache, fwd_cache, bwd_cache = {}, {}, {}

        def jinit(mod):
            if mod not in init_cache:
                init_cache[mod] = jax.jit(mod.init)
            return init_cache[mod]

        def fwd(mod):
            if mod not in fwd_cache:
                fwd_cache[mod] = jax.jit(
                    lambda p, x: mod.apply({"params": p}, x))
            return fwd_cache[mod]

        def bwd(mod):
            if mod not in bwd_cache:
                def f(p, x, ct):
                    _, vjp = jax.vjp(
                        lambda p, x: mod.apply({"params": p}, x), p, x)
                    return vjp(ct)
                bwd_cache[mod] = jax.jit(f)
            return bwd_cache[mod]

        # zero.Init: masters are born ON THE HOST — each layer's init runs
        # on the CPU backend (JAX RNG is bit-deterministic across
        # backends) and inter-layer shapes propagate via eval_shape, so
        # NO parameter bytes ever cross the accelerator link at init.
        # Init inputs are zeros (param shapes here don't depend on input
        # values).
        try:
            cpu_dev = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # pragma: no cover — cpu backend always exists
            cpu_dev = None
        rng = jax.random.PRNGKey(seed)
        x_aval = jax.eval_shape(lambda b: jnp.asarray(self.input_fn(b)),
                                sample_batch)
        batch_zeros = jax.tree.map(
            lambda l: np.zeros(np.shape(l), np.asarray(l).dtype),
            sample_batch)
        with jax.default_device(cpu_dev):
            for i, m in enumerate(self.layers):
                lrng = jax.random.fold_in(rng, i)
                x_zero = jnp.zeros(x_aval.shape, x_aval.dtype)
                if i < len(self.layers) - 1:
                    variables = jinit(m)(lrng, x_zero)
                    x_aval = jax.eval_shape(
                        lambda p, xx, mod=m: mod.apply({"params": p}, xx),
                        variables["params"], x_aval)
                else:
                    variables = jinit(m)(lrng, x_zero, batch_zeros)
                self.store.add_layer(variables["params"])
                del variables  # host master is authoritative
        # moments live with the masters (RAM; the optimizer-state NVMe
        # swapper in zero/offload.py covers disk-resident moments)
        self._m = [[np.zeros_like(h) for h in self.store.host_leaves(i)]
                   for i in range(len(self.layers))]
        self._v = [[np.zeros_like(h) for h in self.store.host_leaves(i)]
                   for i in range(len(self.layers))]

        self._fwd = [fwd(m) for m in self.layers[:-1]]
        self._bwd = [bwd(m) for m in self.layers[:-1]]
        head = self.layers[-1]
        self._head_grad = jax.jit(jax.value_and_grad(
            lambda p, x, b: head.apply({"params": p}, x, b), argnums=(0, 1)))
        log_dist(f"Zero3OffloadEngine: {len(self.layers)} layers, "
                 f"{self.store.total_param_bytes / 2**20:.1f} MiB params "
                 f"host-resident ({'nvme' if nvme_path else 'cpu'})",
                 ranks=[0])

    # ------------------------------------------------------------------ train
    def train_batch(self, batch=None):
        L = len(self.layers)
        dt = self.compute_dtype
        x = jnp.asarray(self.input_fn(batch))
        if jnp.issubdtype(x.dtype, jnp.floating):  # token ids stay integer
            x = x.astype(dt)

        # forward sweep: fetch i, prefetch i+1, compute, release
        acts = [x]
        p_cur = self.store.fetch(0, dt)
        for i in range(L - 1):
            self.store.fetch(i + 1, dt)          # double buffer: next layer
            x = self._fwd[i](p_cur, x)
            acts.append(x)
            self.store.release(i)
            p_cur = self.store.fetch(i + 1, dt)

        # loss head: value + grads wrt (params, input)
        loss, (g_head, ct) = self._head_grad(
            self.store.fetch(L - 1, dt), acts[-1], batch)
        grads = {L - 1: self._to_host(g_head)}
        self.store.release(L - 1)

        # backward sweep: re-fetch, recompute VJP, stream grads to host
        for i in reversed(range(L - 1)):
            if i - 1 >= 0:
                self.store.fetch(i - 1, dt)      # double buffer: prev layer
            g_p, ct = self._bwd[i](self.store.fetch(i, dt), acts[i], ct)
            grads[i] = self._to_host(g_p)
            self.store.release(i)

        self._step(grads)
        self.global_steps += 1
        return loss

    def _to_host(self, grad_tree) -> List[np.ndarray]:
        return [np.asarray(jax.device_get(g), np.float32)
                for g in jax.tree.leaves(grad_tree)]

    def _step(self, grads):
        """Host Adam sweep, one layer at a time (NVMe masters visit RAM
        only for their own update — the PartitionedOptimizerSwapper
        access pattern)."""
        step_no = self.global_steps + 1
        for i in range(len(self.layers)):
            masters = self.store.host_leaves(i)
            for p, g, m, v in zip(masters, grads[i], self._m[i], self._v[i]):
                self._adam.step_leaf(step_no, self.lr, p, g, m, v)
            self.store.write_back(i, masters)

    # ------------------------------------------------------------ checkpoint
    def state_dict(self):
        # deep-copy: the masters/moments are mutated in place every step
        return {
            "params": [[np.array(h) for h in self.store.host_leaves(i)]
                       for i in range(len(self.layers))],
            "exp_avg": [[np.array(a) for a in layer] for layer in self._m],
            "exp_avg_sq": [[np.array(a) for a in layer] for layer in self._v],
            "step": self.global_steps,
        }

    def load_state_dict(self, sd):
        for i, leaves in enumerate(sd["params"]):
            masters = self.store.host_leaves(i)
            for dst, src in zip(masters, leaves):
                np.copyto(dst, src)
            self.store.write_back(i, masters)
        self._m = [[np.array(a) for a in layer] for layer in sd["exp_avg"]]
        self._v = [[np.array(a) for a in layer] for layer in sd["exp_avg_sq"]]
        self.global_steps = sd["step"]

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """Engine-compatible file layout: one model-states file holding
        the layered masters + moments (single-process engine — the dp=1
        analogue of runtime/checkpoint_io.py), plus the `latest` tag."""
        import pickle

        from deepspeed_tpu.runtime.engine import (LATEST_FILE,
                                                  MODEL_FILE_SUFFIX)
        if tag is None:
            tag = f"global_step{self.global_steps}"
        tag_dir = os.path.join(save_dir, str(tag))
        os.makedirs(tag_dir, exist_ok=True)
        sd = self.state_dict()
        sd["client_state"] = client_state or {}
        with open(os.path.join(tag_dir, f"mp_rank_00{MODEL_FILE_SUFFIX}"),
                  "wb") as f:
            pickle.dump(sd, f)
        if save_latest:
            with open(os.path.join(save_dir, LATEST_FILE), "w") as f:
                f.write(str(tag))
        return True

    def load_checkpoint(self, load_dir, tag=None):
        import pickle

        from deepspeed_tpu.runtime.engine import (LATEST_FILE,
                                                  MODEL_FILE_SUFFIX)
        if tag is None:
            latest = os.path.join(load_dir, LATEST_FILE)
            if not os.path.exists(latest):
                # engine contract (engine.py load_checkpoint): resume-if-
                # present — a fresh run starts from scratch, no crash
                log_dist(f"no '{LATEST_FILE}' file under {load_dir}; "
                         "starting from scratch", ranks=[0])
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        path = os.path.join(load_dir, str(tag),
                            f"mp_rank_00{MODEL_FILE_SUFFIX}")
        with open(path, "rb") as f:
            sd = pickle.load(f)
        client_state = sd.pop("client_state", {})
        self.load_state_dict(sd)
        return path, client_state


class _HostAdam:
    """One Adam leaf update on host buffers: the AVX C++ kernel when it
    builds (csrc/cpu_adam.cpp via CPUAdamBuilder), else vectorised numpy.
    Kept per-leaf (not list-bound like DeepSpeedCPUAdam) so NVMe-resident
    masters can stream through RAM one layer at a time."""

    def __init__(self, betas, eps, weight_decay, adamw_mode):
        self.b1, self.b2 = betas
        self.eps = eps
        self.wd = weight_decay
        self.adamw = adamw_mode
        self.lib = None
        self.opt_id = None
        try:
            from deepspeed_tpu.ops.op_builder.builder import CPUAdamBuilder
            if CPUAdamBuilder().is_compatible():
                from deepspeed_tpu.ops.adam import cpu_adam as _ca
                self.lib = CPUAdamBuilder().load()
                self.opt_id = next(_ca._ids)
                self.lib.ds_adam_create(self.opt_id, self.b1, self.b2, eps,
                                        weight_decay, 1 if adamw_mode else 0)
        except Exception:  # pragma: no cover — numpy fallback always works
            self.lib = None

    def step_leaf(self, step_no, lr, p, g, m, v):
        g = np.ascontiguousarray(g, np.float32)
        if self.lib is not None:
            from deepspeed_tpu.ops.adam.cpu_adam import _ptr
            rc = self.lib.ds_adam_step(self.opt_id, step_no, lr, _ptr(p),
                                       _ptr(g), _ptr(m), _ptr(v), p.size)
            assert rc == 0, f"ds_adam_step failed ({rc})"
            return
        if self.adamw:
            p *= (1.0 - lr * self.wd)
        elif self.wd:
            g = g + self.wd * p
        m *= self.b1
        m += (1 - self.b1) * g
        v *= self.b2
        v += (1 - self.b2) * g * g
        mh = m / (1 - self.b1 ** step_no)
        vh = v / (1 - self.b2 ** step_no)
        p -= lr * mh / (np.sqrt(vh) + self.eps)

    def __del__(self):
        if self.lib is not None:
            try:
                self.lib.ds_adam_destroy(self.opt_id)
            except Exception:
                pass
