"""The ``nemotron_h`` reference, binding and metrics at a test size on the CPU
(``tiny_nemotron_h.json``: the shape of
``configs/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16.json`` with every size cut):
what the program serves through its paged K/V pools, its per-slot state and
its held experts against the plain reference's full forward; the grouped
``B``/``C`` scan and decode kernel (interpret mode) against a float64
recurrence; the squared-ReLU experts against a loop over tokens; the two
halves of the experts adding up to the uncut layer; the cell's kind through
``run.measure``; the new reader on canned records. A CPU run yields counts
and comparisons, never a speed."""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, flops_experts, harness, program_spans
from benchmark.reference import nemotron_h as ref
from benchmark.run import measure

TINY = json.loads(Path(__file__).with_name(
    "tiny_nemotron_h.json").read_text())
FULL = json.loads((harness.HERE / "configs"
                   / "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16.json").read_text())
SEED = 4_200_000_017


def tiny_cell(precision="bfloat16", **traffic):
    tr = {**TINY["traffic"]["tiny-backlog"], **traffic}
    return harness.Cell(
        name="tiny.nemotron_h", config={**TINY["config"],
                                        "precision": precision},
        traffic=tr, chips=1,
        end_to_end=({"name": "serve_tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}),
        per_layer=(), limits=TINY["limits"]["serve"])


def _engine(config, dtype, **serving):
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    program = harness.load_named("programs", "nemotron_h")
    groups.destroy()
    groups.initialize(devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        program.model(config), dtype=dtype,
        params=ref.make_weights(ref.seed_words(SEED), ref.sizes(config),
                                dtype))
    return deepspeed_tpu.init_serving(engine=engine,
                                      config={"serving": serving})


# ------------- (a) prefill, then decode through the cache = the reference
def _served_logits(precision, prompts, n_decode, block_size=8, chunk=7):
    """Logits at every position of every row: chunked prefill of each
    prompt alone (its slot's state carried from chunk to chunk), then
    ``n_decode`` decode steps of both slots together (teacher-forced), all
    through the server's one forward, its paged K/V pools, its state pools
    and its held experts."""
    from deepspeed_tpu.utils import groups
    config = {**TINY["config"], "precision": precision}
    srv = _engine(config, getattr(jnp, precision), max_batch=len(prompts),
                  block_size=block_size, prefill_chunk=chunk,
                  max_model_len=128)
    params, pools = srv.engine.params, srv.pools
    rng = np.random.default_rng(1)
    free = iter(rng.permutation(np.arange(1, srv.cache.num_blocks)))
    bt = np.zeros((len(prompts), srv.max_blocks_per_seq), np.int32)
    for b, ids in enumerate(prompts):                   # scattered blocks
        for i in range(-(-len(ids) // block_size)):
            bt[b, i] = next(free)
    forward = jax.jit(srv.runner._forward)
    logits = [[] for _ in prompts]
    n_prefill = [len(ids) - n_decode for ids in prompts]
    for b, ids in enumerate(prompts):
        for start in range(0, n_prefill[b], chunk):
            n = min(chunk, n_prefill[b] - start)
            tok = np.zeros((1, chunk), np.int32)
            tok[0, :n] = ids[start:start + n]
            idx = np.arange(chunk)
            pools, out, _ = forward(
                params, {}, pools, jnp.asarray(bt[b:b + 1]),
                jnp.asarray([start]), jnp.asarray(tok),
                jnp.asarray(start + idx)[None], jnp.asarray(idx < n)[None],
                slot=jnp.int32(b))
            logits[b].append(np.asarray(out)[:n])
    for step in range(n_decode):
        pos = np.array([n + step for n in n_prefill], np.int32)
        tok = np.array([ids[p] for ids, p in zip(prompts, pos)], np.int32)
        pools, out, _ = forward(
            params, {}, pools, jnp.asarray(bt), jnp.asarray(pos),
            jnp.asarray(tok)[:, None], jnp.asarray(pos)[:, None],
            jnp.ones((len(prompts), 1), bool))
        for b in range(len(prompts)):
            logits[b].append(np.asarray(out)[b:b + 1])
    srv.close()
    groups.destroy()
    return [np.concatenate(rows) for rows in logits], config


# float32 through the pools, the chunked scan, the per-token update and the
# grouped products against float32 at HIGHEST in blocks of the dual form:
# only the order of the sums differs (3.3e-7 at most here, where a
# position's logits reach 0.68)
TOLERANCE = 1e-6


@pytest.fixture(scope="module")
def two_slots():
    rng = np.random.default_rng(0)
    V = TINY["config"]["vocab_size"]
    # 43 and 41 tokens, the last 24 of each decoded: prefill of 19 and 17
    # in chunks of 7 (three each, the last part empty), blocks of 8 ending
    # elsewhere, and the two slots decode at positions 19.. and 17..
    return [rng.integers(0, V, n).astype(np.int32) for n in (43, 41)]


def test_served_logits_equal_the_reference_forward(two_slots):
    got, config = _served_logits("float32", two_slots, n_decode=24)
    for ids, mine in zip(two_slots, got):
        want = ref.full_forward(config, SEED, ids[None])[0]
        assert mine.shape == want.shape
        assert np.abs(mine - want).max() < TOLERANCE
        assert (mine.argmax(-1) == want.argmax(-1)).all()


def test_a_bfloat16_program_is_outside_that_tolerance(two_slots):
    got, config = _served_logits("bfloat16", two_slots, n_decode=24)
    want = ref.full_forward(config, SEED, two_slots[0][None])[0]
    assert 50 * TOLERANCE < np.abs(got[0] - want).max() < 0.02


def test_the_control_reads_far_from_the_reference(two_slots):
    config = TINY["config"]
    ids = two_slots[0][None]
    sound = ref.full_forward(config, SEED, ids)
    control = ref.full_forward(config, SEED, ids, quant=True)
    assert np.abs(control - sound).max() > 1000 * TOLERANCE


# ----------- (b) grouped B and C: the scan and the kernel = the recurrence
def _recurrence64(x, b, c, dt, a, s):
    """A head at a time, a token at a time, in float64: head ``h`` reads
    group ``h // (H / G)``."""
    T, H = dt.shape
    G = b.shape[1]
    ys = np.zeros(x.shape)
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            s[h] = np.exp(dt[t, h] * a[h]) * s[h] \
                + dt[t, h] * np.outer(x[t, h], b[t, g])
            ys[t, h] = s[h] @ c[t, g]
    return ys, s


def _grouped_inputs(T, H, P, N, G, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((T, H, P)), rng.standard_normal((T, G, N)),
            rng.standard_normal((T, G, N)),
            np.logaddexp(0.0, rng.standard_normal((T, H)) - 2.0),
            -rng.uniform(1, 16, H), rng.standard_normal((H, P, N)))


GROUPED = {"G1": (16, 64, 1), "G8": (16, 64, 8), "G8-rows-of-4": (64, 64, 8)}


@pytest.mark.parametrize("shape", list(GROUPED))
def test_the_grouped_chunk_scan_equals_the_recurrence(shape):
    from deepspeed_tpu.ops import ssm
    H, P, G = GROUPED[shape]
    x, b, c, dt, a, s0 = _grouped_inputs(13, H, P, 16, G)
    y, s_end = ssm.chunk_scan(*(jnp.asarray(v, jnp.float32)
                                for v in (x, b, c, dt, a, s0)))
    want_y, want_s = _recurrence64(x, b, c, dt, a, s0.copy())
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_end, want_s, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("path", ["jnp", "kernel"])
@pytest.mark.parametrize("shape", list(GROUPED))
def test_the_grouped_decode_update_equals_the_recurrence(shape, path,
                                                         monkeypatch):
    """Three slots, one of them frozen, six tokens through one pool layer
    of two: the ``ssm_decode`` kernel (interpret mode) and its jnp form
    give what the float64 recurrence gives, and the frozen slot and the
    other layer keep their rows."""
    from deepspeed_tpu.ops import ssm
    from deepspeed_tpu.ops.ssm import decode as ssm_decode
    if path == "kernel":
        monkeypatch.setattr(ssm_decode, "decode_kernel_runs", lambda: True)
        monkeypatch.setattr(ssm_decode, "_decode_call", functools.partial(
            ssm_decode._decode_call, interpret=True))
    H, P, G = GROUPED[shape]
    N, T, B = 16, 6, 3
    ins = [_grouped_inputs(T, H, P, N, G, seed) for seed in range(B)]
    states = [i[5].copy() for i in ins]
    pool = jnp.stack([jnp.zeros((B, ssm.packed_rows(H, P), N, 128)),
                      jnp.stack([ssm.from_heads(jnp.asarray(s, jnp.float32))
                                 for s in states])])
    live = jnp.asarray([True, False, True])
    update = jax.jit(ssm.decode_update)
    for t in range(T):
        y, pool = update(
            pool, 1, *(jnp.asarray(np.stack([i[j][t] for i in ins]),
                                   jnp.float32) for j in range(3)),
            jnp.asarray(np.stack([i[3][t] for i in ins]), jnp.float32),
            jnp.asarray(ins[0][4], jnp.float32), live,
            jnp.zeros((B,), bool))
        for s in (0, 2):
            x, b, c, dt, _, _ = ins[s]
            want, states[s] = _recurrence64(
                x[t:t + 1], b[t:t + 1], c[t:t + 1], dt[t:t + 1], ins[0][4],
                states[s])
            np.testing.assert_allclose(y[s], want[0], rtol=1e-4, atol=1e-4)
    for s in (0, 2):
        np.testing.assert_allclose(ssm.to_heads(pool[1, s], H, P),
                                   states[s], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ssm.to_heads(pool[1, 1], H, P), ins[1][5],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(pool[0], 0)


def test_groups_that_split_a_row_are_refused_by_name():
    from deepspeed_tpu.serving.runner import ServingNotSupported
    config = {**TINY["config"], "n_groups": 4}      # 2 heads of 32 a group
    with pytest.raises(ServingNotSupported, match="grouped B and C"):
        _engine(config, jnp.float32, max_batch=2)


# ----------------------- (c) the squared-ReLU experts = a loop over tokens
@pytest.mark.parametrize("real_rows", [None, 5])
def test_relu2_held_experts_equal_a_loop_over_them(real_rows):
    from deepspeed_tpu.moe.held_experts import held_expert_mlp
    N, E, M, X, k = 13, 32, 24, 8, 3
    first, held = 2, 4
    rng = np.random.default_rng(0)
    h = rng.standard_normal((N, E)).astype(np.float32)
    up = (0.2 * rng.standard_normal((held, M, E))).astype(np.float32)
    down = (0.2 * rng.standard_normal((held, M, E))).astype(np.float32)
    chosen = np.stack([rng.permutation(X)[:k] for _ in range(N)])
    weights = rng.random((N, k)).astype(np.float32)
    real = np.ones(N, bool)
    if real_rows is not None:
        real[real_rows:] = False
    out, counts = held_expert_mlp(
        jnp.asarray(h), jnp.asarray(chosen), jnp.asarray(weights),
        {"up": jnp.asarray(up), "down": jnp.asarray(down)}, first,
        jnp.asarray(real), "relu2")
    want = np.zeros((N, E), np.float32)
    load = np.zeros(held, int)
    for n in range(N):
        for e, w in zip(chosen[n], weights[n]):
            if first <= e < first + held and real[n]:
                mid = np.maximum(up[e - first] @ h[n], 0.0) ** 2
                want[n] += w * (mid @ down[e - first])
                load[e - first] += 1
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=2e-5)
    assert list(np.asarray(counts)) == [load.sum(),
                                        real.sum() * k - load.sum(),
                                        load.max(), (load > 0).sum()]


# ---------------- (d) the two chips' shares add up to the uncut reference
def test_the_two_shares_add_up_to_the_uncut_expert_layer():
    """At the test size, the expert layer of the reference given ALL 16
    experts against the program's parts: experts 0-7 and experts 8-15,
    each routed over all 16 by its own chip, plus the shared expert,
    which both chips compute alike, counted once."""
    from deepspeed_tpu.moe.held_experts import held_expert_mlp, route
    from deepspeed_tpu.serving.runner import _mlp
    X = TINY["config"]["published"]["n_routed_experts"]
    whole = {**TINY["config"], "n_routed_experts": X,
             "deployment": {**TINY["config"]["deployment"],
                            "experts_held": [0, X]}}
    sz = ref.sizes(whole)
    p = ref.layer_as_served(ref.seed_words(SEED), 1, sz, jnp.float32)
    assert sz.kinds[1] == "E"
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (ref.BLOCK, sz.E)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = ref._expert_block(x, p, sz=sz, cap=ref.BLOCK,
                                  quant=False) - x
        m = p["moe"]
        h = ref._rms(x, p["norm_1"], sz.eps)
        chosen, weights = route(h, m["router"], m["router_bias"], k=sz.k,
                                n_group=1, topk_group=1,
                                scale=sz.route_scale)
        real = jnp.ones((ref.BLOCK,), bool)
        parts = [held_expert_mlp(
            h, chosen, weights,
            {n: w[lo:lo + X // 2] for n, w in m["experts"].items()}, lo,
            real, "relu2")[0] for lo in (0, X // 2)]
        shared = _mlp(h, m["shared"], "relu2")
    got = parts[0] + parts[1] + shared
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01
    np.testing.assert_allclose(got, uncut, rtol=1e-4, atol=1e-5)


def test_routing_pinned_to_the_blocks_own_rows_changes_nothing():
    """The reference's expert block routed by other rows (the routing
    witness's pinned forward) is the plain block when those rows are its
    own, and another block when they are not."""
    sz = ref.sizes(TINY["config"])
    p = ref.layer_as_served(ref.seed_words(SEED), 1, sz, jnp.float32)
    rng = np.random.default_rng(5)
    x, other = (jnp.asarray(rng.standard_normal((ref.BLOCK, sz.E)),
                            jnp.float32) for _ in range(2))
    block = functools.partial(ref._expert_block, p=p, sz=sz, cap=ref.BLOCK,
                              quant=False)
    np.testing.assert_array_equal(block(x, route=x), block(x))
    assert np.abs(np.asarray(block(x, route=other) - block(x))).max() > 1e-3


def test_the_routing_witness_runs_at_the_test_size(tmp_path, capsys):
    """``tests/perf/route_flips.py`` at the test size: the three forwards
    run, and every number it prints is a share or a gap."""
    import importlib.util
    path = Path(__file__).parents[1] / "perf" / "route_flips.py"
    spec = importlib.util.spec_from_file_location("route_flips", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    config = tmp_path / "tiny.json"
    config.write_text(json.dumps(TINY["config"]))
    assert script.main(["--config-file", str(config), "--rows", "2",
                        "--length", "520"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0.0 <= out["route_flip_share"] <= 1.0
    for name in ("bf16", "pinned"):
        gaps = out[name]
        assert 0.0 <= gaps["gap_mean"] <= gaps["row_mean_max"] \
            <= gaps["gap_max"] < 1.0, gaps


# ------------------------------------ (e) the server, the kind, the binding
def test_one_decode_and_one_prefill_program_and_the_counters_land():
    from deepspeed_tpu.telemetry import metrics
    registry = metrics.get_registry()
    touched = registry.counter("serving_moe_experts_touched_total")
    before = touched.value
    srv = _engine(TINY["config"], jnp.float32, max_batch=3, block_size=8,
                  prefill_chunk=6, max_model_len=128)
    rng = np.random.default_rng(0)
    for n in (5, 19, 33, 7):
        srv.submit(rng.integers(0, 512, n).astype(np.int32),
                   max_new_tokens=9)
    outs = list(srv.serve_forever())
    assert sorted(len(o.tokens) for o in outs) == [9] * 4
    stats = srv.compile_stats()
    assert stats["decode_signatures"] == 1 and stats["retraces"] == 0
    assert stats["prefill_signatures"] == 1
    assert srv.cache.pool_kinds() == {"k": "paged", "v": "paged",
                                      "ssm": "per_slot", "conv": "per_slot"}
    assert srv.pools["ssm"].shape[0] == 2           # the two Mamba layers
    assert srv.pools["k"].shape[0] == srv.cache.num_blocks  # one layer
    # every dispatch's two expert layers touch at most their 8 experts
    assert 0 < touched.value - before
    srv.close()


def test_a_sound_run_is_correct_and_the_control_is_not():
    from benchmark.kinds import serve_closed
    cell = tiny_cell()
    line, checks, out = measure(cell, SEED, 1.0, 0)
    result = json.loads(line)
    assert result["correct"] is True, checks
    assert out["records"]["sample"]["slots_share"] == 1.0
    assert out["records"]["compiles_in_window"] == 0
    control = serve_closed.numbers(cell, SEED, out["evidence"], quant=True)
    failed = [n for n, v in control.items()
              if v > cell.limits[n]["limit"]]
    assert failed, control


def test_the_binding_refuses_a_file_that_disagrees_with_the_program():
    program = harness.load_named("programs", "nemotron_h")
    config = TINY["config"]
    cfg = program.model(config).config
    assert cfg.layer_types == ("mamba", "moe", "mamba", "attention", "moe")
    for change, match in (
            ({"assumed": {**config["assumed"], "kv_row_lanes": 64}},
             "lanes"),
            ({"assumed": {**config["assumed"], "state_dtype": "bfloat16"}},
             "state"),
            ({"num_hidden_layers": 4}, "names 5 layers"),
            ({"n_routed_experts": 4}, "holds 4 routed experts"),
            ({"tie_word_embeddings": True}, "tied head"),
            ({"hybrid_override_pattern": "MEM-E"}, "serves layers"),
            ({"mlp_hidden_act": "gelu"}, "mlp_hidden_act")):
        with pytest.raises(ValueError, match=match):
            program.model({**config, **change})
    cfg = program.model(FULL).config
    assert cfg.n_layer == 9 and cfg.attention_layers == (5,)
    assert cfg.mamba_layers == (0, 2, 4, 7)
    assert cfg.expert_layers == (1, 3, 6, 8)
    assert (cfg.mamba_inner, cfg.conv_channels, cfg.head_dim) == (
        4096, 6144, 128)
    assert (cfg.n_held, cfg.n_routed_experts, cfg.mlp_hidden_act) == (
        64, 128, "relu2")


# --------------------------------------------------- the required operations
def test_required_operations_of_the_configuration_as_run():
    c = FULL
    E, W = 2688, 4096 + 2 * 8 * 128
    mamba = E * (4096 + W + 64) + 4 * W + 4096 * E
    attention = 2 * E * 4096 + 2 * E * 256
    experts = E * 128 + 2 * E * 3712 + 6 * 64 / 128 * 2 * E * 1856
    assert ref._weights_a_token(c) == {"M": mamba, "*": attention,
                                       "E": pytest.approx(experts)}
    per_token = 2 * (4 * mamba + attention + 4 * experts) \
        + 4 * 5 * 64 * 64 * 128
    # one output token after a prompt of 10: positions 0..9 and a logit
    one = ref.serve_flops(c, 10, 0, 1)
    assert one == pytest.approx(10 * per_token + 2 * 65536 * E
                                + 4 * 32 * 128 * sum(range(10)))
    more = ref.serve_flops(c, 10, 1, 3)
    assert more == pytest.approx(2 * per_token + 2 * 2 * 65536 * E
                                 + 4 * 32 * 128 * (10 + 11))
    assert ref.serve_flops(c, 10, 3, 3) == 0
    n = 64 * 64 * 128
    assert ref.ssm_decode_cost(c, 10) == {
        "flops": 10 * 5.0 * n, "bytes": 10 * 4.0 * (2 * n + 2 * 8 * 128)}
    cost = flops_experts.expert_decode_cost(c, 3, 20)
    assert cost["bytes"] == 3 * 2 * E * 1856 * 2 + 20 * (
        E * 2 + 2 * 1856 * 2 + E * 4)
    assert cost["flops"] == 2 * 20 * 2 * E * 1856
    dots = json.loads((harness.HERE / "configs"
                       / "dots.vlm1.inst.json").read_text())
    assert flops_experts.expert_matrices(dots) == 3


# ------------------------------------------- the reader, on canned records
def _ctx(monkeypatch, decode_spans, ops=None, config=FULL):
    events = [{"name": "serving_decode", "ph": "X", "ts": (10.1 + i) * 1e6,
               "dur": 1000.0, "pid": 1, "tid": 1, "args": args}
              for i, args in enumerate(decode_spans)]
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    cell = dataclasses.replace(tiny_cell(), config=config)

    class Trace:
        pass

    trace = Trace()
    trace.ops = ops or {}
    return {"cell": cell, "spans": [("window", 10.0, 10.0 + len(events) + 1)],
            "trace": trace, "device_kind": "TPU v5 lite",
            "records": {"window_s": 2.0}}


def test_the_expert_roofline_reader_counts_touched_experts(monkeypatch):
    spans = [{"batch": 384, "pairs_held": 9000, "pairs_absent": 9000,
              "pairs_max": 200, "experts_touched": 256,
              "decode_pairs_held": 4600}] * 2
    ops = {"expert_decode": 0.02, "expert_decode.3": 0.03,
           "expert_prefill.1": 9.0}
    read = harness.load_reader("offline_expert_decode_roofline")
    got = read(_ctx(monkeypatch, spans, ops))
    cost = flops_experts.expert_decode_cost(FULL, 512, 9200)
    least = flops.roofline_seconds(cost, flops.peaks("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert got == pytest.approx(100 * least["seconds"] / 0.05)
    assert 0 < got < 100
    # no named product on the trace (a program whose products keep
    # megablox's name), or no touched experts counted (a program before
    # them): nothing to read
    assert read(_ctx(monkeypatch, spans, {"gmm": 1.0})) is None
    assert read(_ctx(monkeypatch, [{"batch": 4, "pairs_held": 3,
                                    "pairs_absent": 3, "pairs_max": 1}],
                     ops)) is None
