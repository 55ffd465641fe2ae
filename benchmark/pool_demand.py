"""KV blocks that a ``serve-closed`` cell's schedule holds at once, so that
``deployment.serving.num_blocks`` can be what the traffic fills and no more
(the package default reserves ``max_batch`` x ``n_positions``, of which
this mix fills a quarter):

    JAX_PLATFORMS=cpu python3 benchmark/pool_demand.py --workload <cell> \
        --max-batch 384 --prefill-chunk 128 --steps 20000

The program's own scheduler is driven as the kind drives it (queue primed,
freed slots refilled at the next step), for ``--steps`` steps, on a model
of the test size with the device programs replaced by stubs: the schedule
depends on counts and never on the clock or on a token, so it is the
chip's. Needs no chip. Prints the peak and the mean of the blocks
allocated and of the live tokens (prompt + served, of the requests in a
slot) after each step.
"""

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np      # noqa: E402

SMALL = dict(preset=None, n_embd=32, n_head=2, n_layer=1, vocab_size=256)


def demand(cell, serving: dict, steps: int, stub: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    from benchmark import harness, traffic as traffic_gen

    config = {**copy.deepcopy(cell.config), **SMALL}
    config["assumed"] = {"padded_vocab_size": SMALL["vocab_size"]}
    ref = harness.load_named("reference", config["reference"])
    program = harness.load_named("programs", config["reference"])
    tr, V = cell.traffic, config["vocab_size"]
    groups.destroy()
    groups.initialize(devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        program.model(config), dtype=jnp.bfloat16,
        params=ref.make_weights(ref.seed_words(1), ref.sizes(config),
                                jnp.bfloat16))
    srv = deepspeed_tpu.init_serving(
        engine=engine, config={"serving": {**serving, **tr["serving"]}})
    slots = srv.max_batch
    if stub:
        srv._decode_fn = lambda params, scales, pools, *rest: (
            pools, np.zeros((1, slots), np.int32))
        srv.prefill.prefill_fn = lambda params, scales, pools, *rest: pools
    shapes = traffic_gen.request_shapes(tr)
    depth = int(tr["queue_depth_in_batches"]) * slots
    sent, blocks, tokens = 0, [], []
    for _ in range(steps):
        while srv.scheduler.num_waiting < depth:
            p_len, o_len = shapes[sent % len(shapes)]
            srv.submit(traffic_gen.prompt_ids(1, sent, p_len, V),
                       max_new_tokens=o_len, temperature=0.0,
                       eos_token_id=None)
            sent += 1
        srv.step()
        srv.collect()
        blocks.append(srv.scheduler.allocator.num_allocated)
        tokens.append(sum(len(r.prompt) + len(r.output_tokens)
                          for r in srv.scheduler.slots if r is not None))
    out = {"max_batch": slots, "prefill_chunk": srv.prefill.chunk_size,
           "block_size": srv.cache.block_size, "steps": steps,
           "blocks_peak": int(max(blocks)),
           "blocks_mean": float(np.mean(blocks[steps // 10:])),
           "live_tokens_peak": int(max(tokens)),
           "live_tokens_mean": float(np.mean(tokens[steps // 10:])),
           "preemptions": int(srv.scheduler.preemptions_total),
           "default_num_blocks": 1 + slots * srv.max_blocks_per_seq}
    srv.close()
    groups.destroy()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--max-batch", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20000)
    args = ap.parse_args(argv)
    from benchmark import harness
    cell = harness.load_cell(args.workload)
    serving = {k: v for k, v in cell.config["deployment"]["serving"].items()
               if k != "num_blocks"}
    if args.max_batch:
        serving["max_batch"] = args.max_batch
    if args.prefill_chunk:
        serving["prefill_chunk"] = args.prefill_chunk
    print(json.dumps(demand(cell, serving, args.steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
