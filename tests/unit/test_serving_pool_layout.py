"""The KV pools stay where they lie: a TPU-compile rehearsal, no chip.

Each serving program is lowered with abstract parameters and pools
(``jax.ShapeDtypeStruct`` on a described ``v5e`` device) at the published
widths of the benchmark's two configurations, with the cells' slot and
block counts, and compiled by the installed libtpu from this CPU process.
Nothing runs; what is asserted is the compile's own account:

* the temporaries stay under 5% of the pools' bytes,
* every pool is aliased to its output (the donation is honoured), and
* no ``copy`` in the compiled program has a pool's shape.

The decode and draft programs over bfloat16 pools are compiled as the
chip runs them, with the decode walk's Pallas kernel in them
(``paged_attention`` asks the platform, which is the CPU here, so the
test answers for it): Mosaic builds the kernel for the v5e, and it reads
the pools from HBM where they lie.

The ``[L, N, H, BS, D]`` pool and its ``.at[:, blk, :, off, :]`` write,
which this shape replaced, failed all three: both programs converted the
whole pool there and back (PERF.md, PR 27), and ``test_detector_sees_*``
keeps that pattern as the control that the detector is not blind.

Depth is cut to 2 layers (the layer count multiplies the pool's leading
dimension and nothing else) and the vocabulary to 128 rows: at gpt2-xl's
width the compiler also relayouts the 1,600-wide ``wte`` (161 MB at the
published vocabulary), which is no pool and would drown what is measured,
and the sampler's sort over 50,257 logits is most of a compile's time.

The prefill program is compiled in both its forms: one chunk a dispatch,
and the rows that chunks of 128 take (``prefill_rows``: two). At the
latent and the state-space models' chunk widths (512 and 256) the engine
keeps one row, and the program it dispatches lowers to the text of the
lone-slot program, which one chunk a dispatch ran before rows existed
(``test_a_wide_chunks_prefill_is_the_lone_slot_program``).

The training flash backward's shape rule is held to the same compile at
its edges (``test_flash_backward_fits_the_chip``): which shapes take the
one-pass kernel, and that each compiles within the scoped VMEM.

Every libtpu call sits in a fixture or a test: only the worker that is
given this file may load the library (on-chip-measurement guide, §2).
"""

import functools
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.serving import paged_attention
from deepspeed_tpu.serving.kv_cache import PagedKVCache
from deepspeed_tpu.serving.prefill import ChunkedPrefill, prefill_rows
from deepspeed_tpu.serving.runner import PagedGPT2Runner
from deepspeed_tpu.serving.speculative import SpeculativeDecoder
from deepspeed_tpu.utils import groups

# benchmark/configs/*.json widths; slots and blocks as the serve cells run
# them (40 and 8 slots of 64 blocks of 16, plus the null block)
CELLS = {
    "gpt2-medium": dict(n_embd=1024, n_head=16, slots=40, num_blocks=2561),
    "gpt2-xl": dict(n_embd=1600, n_head=25, slots=8, num_blocks=513),
}
N_LAYER, BLOCK_SIZE, MAX_BLOCKS, CHUNK, SPEC_K = 2, 16, 64, 32, 3
ROWS_CHUNK = 128        # the gpt2 serve cells' chunk: two rows a dispatch
# the decode program's temporaries at these shapes before it took the
# dispatch before's tokens as an input (commit fb6d7b3, this compiler)
DECODE_TEMP_BYTES = {("gpt2-medium", False): 2270720,
                     ("gpt2-medium", True): 3432448,
                     ("gpt2-xl", False): 2254336,
                     ("gpt2-xl", True): 2577920}
HLO_DTYPE = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip):
    """``spec(shape, dtype)``: an abstract array on the described chip."""
    return functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)


def _pool_copies(hlo_text, pool_specs):
    """The ``copy`` instructions whose result has a pool's dtype and
    shape."""
    shapes = {f"{HLO_DTYPE[jnp.dtype(s.dtype).name]}"
              f"[{','.join(map(str, s.shape))}]" for s in pool_specs}
    found = []
    for line in hlo_text.splitlines():
        m = re.search(r"= (\w+\[[\d,]*\])\S* copy\(", line)
        if m and m.group(1) in shapes:
            found.append(line.strip()[:160])
    return found


def _programs(cell, int8_kv, one_chip):
    """name -> (jitted program, abstract arguments), and the pools."""
    spec = _spec(one_chip)
    cfg = GPT2Config(vocab_size=128, n_positions=1024,
                     n_embd=cell["n_embd"], n_layer=N_LAYER,
                     n_head=cell["n_head"])
    model = GPT2LMHeadModel(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0),
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"])
    params = jax.tree.map(lambda a: spec(a.shape, jnp.bfloat16), params)
    cache = PagedKVCache(N_LAYER, cfg.n_head, cfg.n_embd // cfg.n_head,
                         BLOCK_SIZE, cell["num_blocks"],
                         dtype=jnp.bfloat16, int8_kv=int8_kv)
    runner = PagedGPT2Runner(model, cache)
    spec_dec = SpeculativeDecoder(runner, k=SPEC_K, draft_layers=1)
    pools = {name: spec(shape, dtype)
             for name, (shape, dtype) in cache._pool_shapes().items()}
    B, i32, f32 = cell["slots"], jnp.int32, jnp.float32
    R = prefill_rows(ROWS_CHUNK, B)
    slot = [spec((B, MAX_BLOCKS), i32), spec((B,), i32),
            spec((B,), jnp.bool_)]                      # bt, pos, active
    sampling = [spec((B,), f32), spec((B,), f32),
                spec((B, 2), jnp.uint32), spec((B,), i32)]
    return cache, pools, {
        # the last two: the dispatch before's tokens, which the server
        # leaves on the device, and the row of them that is a slot's input
        "decode": (runner._decode,
                   [params, {}, pools, *slot, spec((B,), i32), *sampling,
                    spec((1, B), i32), spec((B,), i32)]),
        "prefill": (runner._prefill,
                    [params, {}, pools, spec((MAX_BLOCKS,), i32),
                     spec((CHUNK,), i32), spec((), i32), spec((), i32)]),
        # R chunks of 128, one a row: tables, tokens, starts, lengths
        "prefill_rows": (runner._prefill,
                         [params, {}, pools, spec((R, MAX_BLOCKS), i32),
                          spec((R, ROWS_CHUNK), i32), spec((R,), i32),
                          spec((R,), i32)]),
        "copy_block": (runner._copy_block,
                       [pools, spec((), i32), spec((), i32)]),
        "draft": (spec_dec._draft,
                  [params, {}, pools, *slot, spec((B,), i32),
                   spec((B,), i32)]),
        "verify": (spec_dec._verify,
                   [params, {}, pools, *slot, spec((SPEC_K, B), i32),
                    spec((B,), i32), *sampling]),
    }


@pytest.mark.parametrize("int8_kv", [False, True], ids=["kv-bf16", "kv-int8"])
@pytest.mark.parametrize("program", ["decode", "prefill", "copy_block",
                                     "draft", "verify"])
@pytest.mark.parametrize("config", list(CELLS))
def test_program_leaves_the_pools_in_place(one_chip, monkeypatch, config,
                                           program, int8_kv):
    # one described chip and no mesh, as the serve cells run
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    monkeypatch.setattr(groups, "_MESH", None)
    cache, pools, programs = _programs(CELLS[config], int8_kv, one_chip)
    fn, args = programs[program]
    compiled = fn.lower(*args).compile()
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    # the draft is one layer under a scan over its steps
    walks = {"decode": N_LAYER, "draft": 1}.get(program, 0)
    assert kernels == (0 if int8_kv else walks), (
        f"{kernels} Mosaic calls in the {program} program")
    mem = compiled.memory_analysis()
    pool_bytes = cache.pool_bytes()
    if program == "decode":
        # reading a slot's input from the dispatch before's tokens adds
        # one [slots] select and no temporary to speak of: three of the
        # four compile to the byte what they did without it, gpt2-xl
        # over bfloat16 pools to 31.5 KB more
        assert mem.temp_size_in_bytes <= (
            DECODE_TEMP_BYTES[config, int8_kv] + 64 * 1024), (
            f"{mem.temp_size_in_bytes} bytes of temporaries")
    assert mem.temp_size_in_bytes < 0.05 * pool_bytes, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries beside "
        f"{pool_bytes / 1e6:.1f} MB of pools")
    assert mem.alias_size_in_bytes >= pool_bytes, (
        f"only {mem.alias_size_in_bytes} of the pools' {pool_bytes} "
        f"bytes are updated in place")
    copies = _pool_copies(compiled.as_text(), pools.values())
    assert not copies, "whole-pool copies:\n" + "\n".join(copies)


@pytest.mark.parametrize("int8_kv", [False, True], ids=["kv-bf16", "kv-int8"])
@pytest.mark.parametrize("config", list(CELLS))
def test_the_rows_prefill_program_leaves_the_pools_in_place(
        one_chip, monkeypatch, config, int8_kv):
    """The prefill program at two rows of 128 (a call of two chunks, or
    of one run alone: both branches in one program) updates every pool in
    place and copies none whole. Its temporaries are the two rows'
    activations: 5.6-7.6 MB at these widths, 3.7-4.4 times the one-chunk
    program's at 128 tokens (this compiler), where one pool copied whole
    would add 71-336 MB (the bound on the programs above, 5% of the
    pools, is under a two-row call's activations at gpt2-xl's)."""
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    monkeypatch.setattr(groups, "_MESH", None)
    cache, pools, programs = _programs(CELLS[config], int8_kv, one_chip)
    fn, args = programs["prefill_rows"]
    assert args[4].shape == (2, ROWS_CHUNK)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 0
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e6, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries")
    assert mem.alias_size_in_bytes >= cache.pool_bytes()
    copies = _pool_copies(text, pools.values())
    assert not copies, "whole-pool copies:\n" + "\n".join(copies)


def test_detector_sees_the_stacked_write_convert_the_pool(one_chip):
    """The control: the pool as it was (``[L, N, H, BS, D]``, one scatter
    for all layers with a ``:`` over layers and over heads ahead of the
    indexed dimensions) compiles to whole-pool copies and temporaries of
    the pools' own size, and the detector above says so."""
    L, N, H, BS, D, B = 4, 2561, 16, 16, 64, 40
    spec = _spec(one_chip)

    def step(k_pool, new, ids, blk, off):
        read = k_pool[1, ids].astype(jnp.float32).sum()
        return k_pool.at[:, blk, :, off, :].set(new), read

    pool = spec((L, N, H, BS, D), jnp.bfloat16)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        pool, spec((B, L, H, D), jnp.bfloat16), spec((B,), jnp.int32),
        spec((B,), jnp.int32), spec((B,), jnp.int32)).compile()
    assert _pool_copies(compiled.as_text(), [pool])
    pool_bytes = L * N * H * BS * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes >= pool_bytes


# ----------------------------------------------- the latent pool (PR 35)
def _latent_programs(one_chip, slots=16, num_blocks=257, chunk=128):
    """The decode, prefill and block-copy programs of the latent-attention
    model at its published widths (benchmark/configs/dots.vlm1.inst.json),
    one dense and two expert layers, 128 rows of vocabulary."""
    from deepspeed_tpu.models.mla_moe import (MLAMoEConfig,
                                              MLAMoEForCausalLM, init_params)
    from deepspeed_tpu.serving.runner import PagedRunner, cache_rows
    spec = _spec(one_chip)
    cfg = MLAMoEConfig(
        vocab_size=128, hidden_size=7168, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=18432,
        moe_intermediate_size=2048, n_routed_experts=256,
        experts_held=(0, 16), num_experts_per_tok=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, num_attention_heads=128, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, max_position_embeddings=163840, rope_factor=40.0,
        rope_mscale=1.0, rope_mscale_all_dim=1.0)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    params = jax.tree.map(lambda a: spec(a.shape, jnp.bfloat16), params)
    cache = PagedKVCache(n_layer=3, block_size=BLOCK_SIZE,
                         num_blocks=num_blocks, dtype=jnp.bfloat16,
                         **cache_rows(cfg))
    runner = PagedRunner(MLAMoEForCausalLM(cfg), cache)
    pools = {name: spec(shape, dtype)
             for name, (shape, dtype) in cache._pool_shapes().items()}
    B, i32, f32 = slots, jnp.int32, jnp.float32
    return cache, pools, runner, {
        "decode": (runner._decode,
                   [params, {}, pools, spec((B, MAX_BLOCKS), i32),
                    spec((B,), i32), spec((B,), jnp.bool_), spec((B,), i32),
                    spec((B,), f32), spec((B,), f32),
                    spec((B, 2), jnp.uint32), spec((B,), i32),
                    spec((1, B), i32), spec((B,), i32)]),
        "prefill": (runner._prefill,
                    [params, {}, pools, spec((MAX_BLOCKS,), i32),
                     spec((chunk,), i32), spec((), i32), spec((), i32)]),
        "copy_block": (runner._copy_block,
                       [pools, spec((), i32), spec((), i32)]),
    }


@pytest.mark.parametrize("program", ["decode", "prefill", "copy_block"])
def test_latent_program_leaves_its_one_pool_in_place(one_chip, monkeypatch,
                                                     program):
    """The latent model's programs compile for the v5e at the published
    widths: one ``kv`` pool of 640-lane rows, aliased to its output and
    never copied whole; the decode walk is the ``paged_decode`` kernel (one
    call a layer, the queries 128 dense rows a slot), and an expert layer
    two grouped products (Mosaic as well, named by the phase: the trace's
    operations that the expert roofline reads). A prefill chunk needs no
    head, so the compiler drops the last layer's MLP and its two
    products."""
    from deepspeed_tpu.moe import held_experts
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    monkeypatch.setattr(held_experts, "_interpret", lambda: False)
    monkeypatch.setattr(groups, "_MESH", None)
    cache, pools, _, programs = _latent_programs(one_chip)
    assert {n: tuple(s.shape) for n, s in pools.items()} == {
        "kv": (3 * 257, 16, 640)}
    fn, args = programs[program]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert kernels == {"decode": 3 + 4, "prefill": 2, "copy_block": 0}[
        program], f"{kernels} Mosaic calls in the {program} program"
    calls = re.findall(r"%([A-Za-z_]+)[.\d]* = [^\n]*tpu_custom_call", text)
    assert sorted(set(calls)) == {
        "decode": ["expert_decode", "paged_decode"],
        "prefill": ["expert_prefill"], "copy_block": []}[program]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache.pool_bytes()
    copies = _pool_copies(text, pools.values())
    assert not copies, "whole-pool copies:\n" + "\n".join(copies)
    if program == "decode":
        assert mem.temp_size_in_bytes < 64e6


# ------------------- the decode kernel at the serve cells' slots (PR 36)
# slots, table width and blocks a layer as benchmark/configs/*.json run
# them; the ring of VMEM buffers has to fit the default scoped VMEM
# limit (16 MiB) beside a slot's pipelined rows
KERNEL_CELLS = {
    "gpt2-medium-384-slots": dict(B=384, H=16, W=1024, MB=64, N=7305),
    "gpt2-xl-96-slots": dict(B=96, H=25, W=1664, MB=64, N=1949),
    "dots-latent-96-slots": dict(B=96, H=128, W=640, MB=288, N=10724),
}


@pytest.mark.parametrize("cell", list(KERNEL_CELLS))
def test_decode_kernel_fits_the_chip_at_the_cells_slots(one_chip, cell):
    """One layer's ``paged_decode`` call alone, compiled for the v5e at
    the slot counts the serve cells run (the programs above keep the 40
    and 8 slots their temporaries were recorded at): Mosaic accepts the
    ring under the limit it has, no limit is raised, and the call is
    one kernel."""
    c = KERNEL_CELLS[cell]
    spec = _spec(one_chip)
    B, H, W = c["B"], c["H"], c["W"]
    tables = (spec((B, c["MB"]), jnp.int32), spec((B,), jnp.int32))
    pool = spec((2 * c["N"], BLOCK_SIZE, W), jnp.bfloat16)
    if cell.startswith("dots"):
        args = (spec((B, H, 576), jnp.bfloat16), spec((B, 576), jnp.bfloat16),
                None, spec((), jnp.int32), pool, None, *tables, 576 ** -0.5)
        kw = dict(v_width=512)
    else:
        row = spec((B, H, 64), jnp.bfloat16)
        args = (row, row, row, spec((), jnp.int32), pool, pool, *tables,
                0.125)
        kw = {}
    compiled = paged_attention._decode_kernel_call.lower(
        *args, **kw).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "vmem_limit_bytes" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6


# ------------------------------------- the per-slot state pools (the hybrid)
def _state_programs(one_chip, slots=64, num_blocks=1375, chunk=256):
    """The decode and prefill programs of the state-space hybrid at the
    published widths of benchmark/configs/granite-4.0-h-micro.json, two
    Mamba layers around one attention layer, 128 rows of vocabulary, at
    the cell's slots, blocks and chunk."""
    from deepspeed_tpu.models.ssm_hybrid import (SSMHybridConfig,
                                                 SSMHybridForCausalLM,
                                                 init_params)
    from deepspeed_tpu.serving.runner import (PagedRunner, cache_layers,
                                              cache_rows)
    spec = _spec(one_chip)
    cfg = SSMHybridConfig(
        vocab_size=128, hidden_size=2048, intermediate_size=8192,
        layer_types=("mamba", "attention", "mamba"), num_attention_heads=32,
        num_key_value_heads=8, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, max_position_embeddings=131072,
        embedding_multiplier=12.0, attention_multiplier=0.015625,
        residual_multiplier=0.22, logits_scaling=8.0)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    params = jax.tree.map(lambda a: spec(a.shape, jnp.bfloat16), params)
    layers = cache_layers(cfg)
    cache = PagedKVCache(n_layer=layers["paged"], block_size=BLOCK_SIZE,
                         num_blocks=num_blocks, dtype=jnp.bfloat16,
                         state_layers=layers["per_slot"], slots=slots,
                         **cache_rows(cfg))
    runner = PagedRunner(SSMHybridForCausalLM(cfg), cache)
    pools = {name: spec(shape, dtype)
             for name, (shape, dtype) in cache._pool_shapes().items()}
    B, i32, f32 = slots, jnp.int32, jnp.float32
    return cache, pools, runner, {
        "decode": (runner._decode,
                   [params, {}, pools, spec((B, MAX_BLOCKS), i32),
                    spec((B,), i32), spec((B,), jnp.bool_), spec((B,), i32),
                    spec((B,), f32), spec((B,), f32),
                    spec((B, 2), jnp.uint32), spec((B,), i32),
                    spec((1, B), i32), spec((B,), i32)]),
        "prefill": (runner._prefill,
                    [params, {}, pools, spec((MAX_BLOCKS,), i32),
                     spec((chunk,), i32), spec((), i32), spec((), i32),
                     spec((), i32)]),
    }


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_state_programs_hold_one_state_pool(one_chip, monkeypatch, program):
    """The hybrid's programs compile for the v5e at the published widths
    and the cell's 64 slots: every pool is aliased to its output, none is
    copied whole, and the temporaries hold no second state pool (a Mamba
    layer writes its slots' state back where it lies, inside the layer
    loop); decode is one ``ssm_decode`` kernel a Mamba layer beside the
    attention layer's ``paged_decode``."""
    from deepspeed_tpu.ops.ssm import decode as ssm_decode
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    monkeypatch.setattr(ssm_decode, "_interpret", lambda: False)
    monkeypatch.setattr(groups, "_MESH", None)
    cache, pools, _, programs = _state_programs(one_chip)
    assert {n: tuple(s.shape) for n, s in pools.items()} == {
        "k": (1375, 16, 512), "v": (1375, 16, 512),
        "ssm": (2, 64, 32, 128, 128), "conv": (2, 64, 3 * 4352)}
    fn, args = programs[program]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == {
        "decode": 3, "prefill": 0}[program]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache.pool_bytes()
    state = cache.pool_bytes("per_slot")
    assert mem.temp_size_in_bytes < 0.25 * state, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries beside "
        f"{state / 1e6:.1f} MB of state pools")
    copies = _pool_copies(text, pools.values())
    assert not copies, "whole-pool copies:\n" + "\n".join(copies)


def _expert_state_programs(one_chip, slots=384, num_blocks=45710, chunk=128):
    """The decode and prefill programs of the hybrid whose layers are one
    part each, at the published widths of
    benchmark/configs/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16.json: a Mamba
    layer (8 groups of B and C), an expert layer (64 held of 128, squared
    ReLU), the attention layer and a second Mamba layer, 128 rows of
    vocabulary, at the cell's slots, blocks and chunk (two rows a prefill
    call)."""
    from deepspeed_tpu.models.ssm_hybrid import (SSMHybridConfig,
                                                 SSMHybridForCausalLM,
                                                 init_params)
    from deepspeed_tpu.serving.runner import (PagedRunner, cache_layers,
                                              cache_rows)
    spec = _spec(one_chip)
    cfg = SSMHybridConfig(
        vocab_size=128, hidden_size=2688, intermediate_size=1856,
        layer_types=("mamba", "moe", "attention", "mamba"),
        num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, mamba_n_heads=64,
        mamba_d_head=64, mamba_d_state=128, mamba_n_groups=8,
        max_position_embeddings=262144, attention_multiplier=128 ** -0.5,
        tie_word_embeddings=False, mlp_after_mixer=False,
        n_routed_experts=128, experts_held=(0, 64), num_experts_per_tok=6,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        routed_scaling_factor=2.5)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    params = jax.tree.map(lambda a: spec(a.shape, jnp.bfloat16), params)
    layers = cache_layers(cfg)
    cache = PagedKVCache(n_layer=layers["paged"], block_size=BLOCK_SIZE,
                         num_blocks=num_blocks, dtype=jnp.bfloat16,
                         state_layers=layers["per_slot"], slots=slots,
                         **cache_rows(cfg))
    runner = PagedRunner(SSMHybridForCausalLM(cfg), cache)
    pools = {name: spec(shape, dtype)
             for name, (shape, dtype) in cache._pool_shapes().items()}
    B, R, i32, f32 = slots, prefill_rows(chunk, slots), jnp.int32, \
        jnp.float32
    return cache, pools, params, {
        "decode": (runner._decode,
                   [params, {}, pools, spec((B, MAX_BLOCKS), i32),
                    spec((B,), i32), spec((B,), jnp.bool_), spec((B,), i32),
                    spec((B,), f32), spec((B,), f32),
                    spec((B, 2), jnp.uint32), spec((B,), i32),
                    spec((1, B), i32), spec((B,), i32)]),
        "prefill": (runner._prefill,
                    [params, {}, pools, spec((R, MAX_BLOCKS), i32),
                     spec((R, chunk), i32), spec((R,), i32), spec((R,), i32),
                     spec((R,), i32)]),
    }


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_expert_state_programs_read_the_weights_where_they_lie(
        one_chip, monkeypatch, program):
    """The one-part-a-layer hybrid's programs compile for the v5e at the
    published widths and the cell's 384 slots: no pool and no weight is
    copied whole (an expert's up matrix held ``[1856, 2688]``, as
    published, fills whole 128-lane tiles; held ``[2688, 1856]`` the
    compiler laid it out the other way and copied all 64 experts' to the
    kernel's layout in every decode step: 0.68 GB of temporaries), the
    temporaries stay under 5% of the state pools, and decode runs an
    ``ssm_decode`` call a Mamba layer, the expert layer's two grouped
    products under their phase's name and one ``paged_decode`` call. (A
    Mamba layer's convolution rows, 14 MB of the pool at 384 slots, are
    still relaid out and back in each decode step: PERF.md, section 7.)"""
    from deepspeed_tpu.moe import held_experts
    from deepspeed_tpu.ops.ssm import decode as ssm_decode
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    monkeypatch.setattr(ssm_decode, "_interpret", lambda: False)
    monkeypatch.setattr(held_experts, "_interpret", lambda: False)
    monkeypatch.setattr(groups, "_MESH", None)
    cache, pools, params, programs = _expert_state_programs(one_chip)
    assert {n: tuple(s.shape) for n, s in pools.items()} == {
        "k": (45710, 16, 256), "v": (45710, 16, 256),
        "ssm": (2, 384, 32, 128, 128), "conv": (2, 384, 3 * 6144)}
    fn, args = programs[program]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([A-Za-z_]+)[.\d]* = [^\n]*tpu_custom_call", text)
    assert sorted(calls) == {
        "decode": ["expert_decode", "expert_decode", "paged_decode",
                   "ssm_decode", "ssm_decode"],
        "prefill": ["expert_prefill", "expert_prefill"]}[program]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache.pool_bytes()
    state = cache.pool_bytes("per_slot")
    assert mem.temp_size_in_bytes < 0.05 * state, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries beside "
        f"{state / 1e6:.1f} MB of state pools")
    copies = _pool_copies(text, list(pools.values()) + [
        a for a in jax.tree.leaves(params) if a.size > 1 << 20])
    assert not copies, "whole-array copies:\n" + "\n".join(copies)


# ------------------ one row a dispatch at the wide chunks (latent, state)
# the cells' slots and chunks (benchmark/configs/*.json)
WIDE_CHUNKS = {"dots": dict(slots=96, chunk=512),
               "granite": dict(slots=64, chunk=256)}
# a Mosaic kernel's body carries the source locations of its caller
_MOSAIC_BODY = re.compile(r"\\22body\\22: \\22[A-Za-z0-9+/=]*\\22")


def _lone_slot_prefill(runner):
    """The prefill program as it was before it took rows: one slot's
    chunk, the forward at ``B = 1`` with no row dimension."""
    def _prefill_impl(params, scales, pools, bt_row, tokens, start,
                      n_valid, slot=None):
        idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        pools, _, counts = runner._forward(
            params, scales, pools, bt_row[None], start[None], tokens[None],
            (start + idx)[None], (idx < n_valid)[None], want_logits=False,
            slot=slot)
        return pools, counts
    return jax.jit(_prefill_impl, donate_argnums=(2,))


@pytest.mark.parametrize("model", list(WIDE_CHUNKS))
def test_a_wide_chunks_prefill_is_the_lone_slot_program(one_chip,
                                                        monkeypatch, model):
    """At chunks of 256 and 512 a dispatch holds one chunk, handed over
    without the row dimension, and the program lowers to the lone-slot
    program's text (the Mosaic kernels' bodies aside, which name their
    caller's source): the two cells run what they ran."""
    from deepspeed_tpu.moe import held_experts
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    monkeypatch.setattr(held_experts, "_interpret", lambda: False)
    monkeypatch.setattr(groups, "_MESH", None)
    cell = WIDE_CHUNKS[model]
    build = _latent_programs if model == "dots" else _state_programs
    _, pools, runner, programs = build(one_chip, chunk=cell["chunk"])
    fn, (params, scales, _, *lone) = programs["prefill"]
    handed = []

    def prefill_fn(params, scales, pools, *args):
        handed.append(args)
        return pools
    planner = ChunkedPrefill(prefill_fn, cell["chunk"], cell["slots"])
    assert planner.rows == 1
    req = types.SimpleNamespace(full_prompt=list(range(2 * cell["chunk"])),
                                cached_len=0, slot=3, block_table=[5, 6],
                                max_cached_len=0)
    planner.dispatch(None, None, None, [planner.plan(req)], MAX_BLOCKS)
    (args,) = handed
    spec = _spec(one_chip)
    args = [spec(np.shape(a), jnp.int32) for a in args]
    assert [a.shape for a in args] == [a.shape for a in lone] + [()] * (
        5 - len(lone))

    def text(f, a):
        return _MOSAIC_BODY.sub("", f.lower(params, scales, pools,
                                            *a).as_text())
    assert text(fn, args) == text(_lone_slot_prefill(runner), args)


# The training flash backward's shape rule (``flash._fused_bwd_fits``),
# held to the v5e's 16 MiB of scoped VMEM here because this is the file
# that describes the chip: (q shape, k shape, dtype, causal) -> Mosaic
# calls of the gradient. 2 = ``flash_fwd`` + the one-pass ``flash_dkv``;
# 3 = the two-call backward, kept where the one pass does not fit.
FLASH_SHAPES = {
    "gpt2_medium_train": ((8, 16, 1024, 64), (8, 16, 1024, 64),
                          jnp.bfloat16, True, 2),
    "bf16_4096_head_256": ((1, 2, 4096, 256), (1, 2, 4096, 256),
                           jnp.bfloat16, True, 2),
    "f32_4096_head_192": ((1, 2, 4096, 192), (1, 2, 4096, 192),
                          jnp.float32, True, 2),
    "f32_4096_head_256": ((1, 2, 4096, 256), (1, 2, 1024, 256),
                          jnp.float32, False, 3),
    "bf16_2048_head_512": ((1, 2, 2048, 512), (1, 2, 2048, 512),
                           jnp.bfloat16, True, 3),
}


@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
def test_flash_backward_fits_the_chip(one_chip, monkeypatch, shape):
    from deepspeed_tpu.ops.transformer import flash
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    monkeypatch.delenv("DS_FLASH_BLOCK", raising=False)
    monkeypatch.delenv("DS_FLASH_STREAM", raising=False)
    qs, ks, dtype, causal, calls = FLASH_SHAPES[shape]
    spec = _spec(one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash.flash_attention(
        q, k, v, causal).astype(jnp.float32)), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(spec(qs, dtype), spec(ks, dtype),
                               spec(ks, dtype)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == calls
