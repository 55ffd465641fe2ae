"""Per-request sampling for the compiled decode step.

The batch-synchronous engine samples with ONE temperature baked into the
compiled loop (a new temperature = a new program). Serving inverts that:
temperature/top-p/seed are *per-request tensors* ``[B]`` flowing through
one compiled program, so any mix of greedy and sampled requests shares
the same decode step.

RNG: every request owns a PRNG key lane (``[B, 2]`` uint32, built host-
side from its seed). Each step folds the slot's current position into its
lane — sampling is deterministic per (seed, position) and independent of
which batch slot or step the token happened to land in, which is what
makes continuous batching reproducible under preemption/resume.

Top-p (nucleus): sort descending, keep the smallest prefix whose
*exclusive* cumulative probability is < p (the top-1 token always
survives), then threshold the unsorted logits — no scatter back through
the sort permutation needed.
"""

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def fold_position_lanes(rng_lanes, positions):
    """Fold each slot's POSITION into its key lane: ``[B, 2]`` uint32
    lanes + ``[B]`` int32 positions -> ``[B, 2]`` folded keys.

    This is THE randomness schedule of the serving engine: a token's draw
    depends only on (request seed, absolute position), never on which
    batch slot, decode_steps grouping, or draft/verify path produced it.
    The decode scan and the speculative verify program both call this
    helper, so speculative acceptance under sampling compares the SAME
    draw sequential decoding would have made at that position.
    """
    return jax.vmap(jax.random.fold_in)(rng_lanes, positions)


def top_p_filter(logits, top_p):
    """Nucleus filter. logits ``[B, V]`` fp32, top_p ``[B]`` in (0, 1];
    p >= 1 keeps everything. Returns filtered logits with non-nucleus
    entries at NEG_INF."""
    sorted_desc = -jnp.sort(-logits, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]       # exclusive cumsum: top-1 stays
    threshold = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1)
    return jnp.where(logits >= threshold[:, None], logits, NEG_INF)


def sample_tokens(logits, temperature, top_p, rng_lanes, positions,
                  vocab_size=None):
    """One sampled token per slot, all policies in one traced program.

    logits ``[B, Vpad]`` fp32; temperature/top_p ``[B]`` fp32 (temperature
    <= 0 means greedy for that slot); rng_lanes ``[B, 2]`` uint32 per-
    request key lanes; positions ``[B]`` int32 (folded into the lane so
    each step draws fresh randomness). ``vocab_size`` masks Megatron-style
    padded vocab rows, which must never be sampled. Returns ``[B]`` int32.
    """
    if vocab_size is not None and vocab_size < logits.shape[-1]:
        logits = logits[:, :vocab_size]
    logits = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    argmax = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def mixed(_):
        safe_t = jnp.where(greedy, 1.0, temperature)
        scaled = logits / safe_t[:, None]
        filtered = top_p_filter(scaled, top_p)
        folded = fold_position_lanes(rng_lanes, positions)
        sampled = jax.vmap(jax.random.categorical)(folded, filtered)
        return jnp.where(greedy, argmax, sampled).astype(jnp.int32)

    # all-greedy batches skip the sort/top-p/categorical work at RUNTIME
    # (lax.cond executes one branch) while staying one compiled program —
    # the decode step is hot enough that the dead sampling machinery was
    # a measurable tax on greedy traffic
    return jax.lax.cond(jnp.all(greedy), lambda _: argmax, mixed,
                        operand=None)


def make_rng_lane(seed: int):
    """One request's key lane (uint32[2]) from its seed: what
    ``jax.random.PRNGKey(seed)`` holds, computed on the host.

    ``submit()`` calls this with the device's queue a whole step deep, so
    a key built on the device and read back would wait for that queue.
    Under the default ``threefry2x32`` implementation with x64 off the
    seed is cut to 32 bits and the key is ``[0, seed mod 2**32]`` (pinned
    against ``PRNGKey`` in tests/unit/test_serving.py); any other
    setting, read off ``jax.config``, keeps the device form."""
    if (jax.config.jax_default_prng_impl == "threefry2x32"
            and not jax.config.jax_enable_x64):
        return np.array([0, int(seed) & 0xFFFFFFFF], np.uint32)
    return np.asarray(jax.device_get(jax.random.PRNGKey(int(seed))),
                      np.uint32)
