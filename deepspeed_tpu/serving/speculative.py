"""Speculative decoding over the paged + prefix-cached KV.

Decode at small batch is weight-bandwidth-bound (PERF.md, the step
anatomy profiler): every generated token streams the full parameter set
for ONE matmul row. Speculative decoding converts that waste into
parallelism — a cheap draft proposes K tokens, then the target model
scores all K+1 positions in ONE forward (near-batch cost in the
bandwidth-bound regime) and keeps the longest prefix it agrees with.

Two compiled programs, both static-shaped for the serving lifetime:

* ``draft_step`` — K greedy steps through the DRAFT. The default draft
  is the truncated-layer self-draft (LayerSkip-style early exit): the
  first ``draft_layers`` of the target's own params pytree plus the
  shared ``ln_f``/tied head — zero extra weights to load, and its layer
  K/V are bit-identical to the target's, so draft writes land in the
  same pools (``write_layers`` over a layer prefix) at the speculative
  positions.
  An explicitly configured small model (``draft_params``) rides the same
  program; draft quality only moves the ACCEPTANCE RATE, never
  correctness — the verify pass decides every delivered token.
* ``verify_step`` — the target forward over ``K+1`` positions per slot
  (the slot's last accepted token + K drafted): the runner's one forward
  at ``C = K+1``, past pages streamed while the candidate chunk stays in
  registers (causal), then ONE stacked scatter writes all layers at all
  candidate positions. Target tokens come from the SAME
  ``sample_tokens`` + position-fold the decode scan uses, so greedy
  verification is argmax-for-argmax the sequential program and sampled
  verification draws the exact (seed, position) stream sequential
  decoding would have drawn.

Rejection is a STATE EDIT, not a recompute: the host simply does not
advance ``cached_len`` past the accepted prefix. Rejected positions keep
stale pool bytes — attention masks every column ``>= past_len``, so they
are invisible until the correct tokens overwrite them. Writes are
budget-masked to the slot's allocated blocks and always land at
positions ``>= cached_len``, which the scheduler keeps strictly outside
prefix-cache-shared (always-full) blocks — speculation can never dirty a
shared or indexed block. The slot-step ledger books the rejected
positions into the ``drafted_rejected`` category so speculation cost is
measured, not hidden (telemetry/serving_observatory.py).

Acceptance rules: ``"exact"`` (default) accepts a drafted token iff it
equals the target's own token for that position — bit-exact parity with
the non-speculative engine for greedy AND sampled requests.
``"typical"`` relaxes sampled slots to accept any draft whose target
probability clears ``typical_threshold`` × the modal probability
(greedy slots stay exact) — higher acceptance, no parity guarantee.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.serving.sampling import sample_tokens


def default_draft_layers(n_layer: int) -> int:
    """Self-draft depth when the config leaves ``draft_layers`` at 0:
    a quarter of the stack (floor 1) — the shallowest exit that keeps
    acceptance useful on well-trained models."""
    return max(1, int(n_layer) // 4)


def validate_draft_params(params, target_params, n_layers: int):
    """An explicit draft must be pool- and head-compatible with the
    target: same embedding width (its K/V land in the target's pools),
    same vocab rows (its argmax is compared against target tokens), and
    at least ``n_layers`` transformer blocks plus the exit pieces."""
    for key in ("wte", "wpe", "ln_f"):
        if key not in params:
            raise ValueError(f"draft params missing {key!r}")
    if params["wte"].shape != target_params["wte"].shape:
        raise ValueError(
            f"draft wte {params['wte'].shape} != target "
            f"{target_params['wte'].shape}: the draft must share the "
            f"target's vocab and embedding width")
    for layer in range(n_layers):
        if f"h_{layer}" not in params:
            raise ValueError(
                f"draft params has no h_{layer} but draft_layers="
                f"{n_layers}")


class SpeculativeDecoder:
    """The two jitted programs + acceptance logic behind the server's
    speculative decode path. Holds NO per-request state — the server
    threads pools/positions exactly as it does for the plain decode
    program, and rollback is the server not advancing ``cached_len``."""

    def __init__(self, runner, *, k, draft_layers=0, acceptance="exact",
                 typical_threshold=0.3, draft_params=None,
                 draft_scales=None):
        assert k >= 1, f"speculative k must be >= 1, got {k}"
        assert acceptance in ("exact", "typical"), acceptance
        self.runner = runner
        self.k = int(k)
        L = runner.cfg.n_layer
        self.draft_layers = (int(draft_layers) if draft_layers
                             else default_draft_layers(L))
        if draft_params is None:
            assert 1 <= self.draft_layers <= L, (
                f"self-draft draft_layers={self.draft_layers} must be in "
                f"[1, n_layer={L}]")
        self.acceptance = acceptance
        self.typical_threshold = float(typical_threshold)
        self.draft_params = draft_params
        self.draft_scales = draft_scales or {}
        # donated pools for the same reason as the runner's programs:
        # the scatters stay in-place and the server re-threads the result
        self._draft = jax.jit(self._draft_impl, donate_argnums=(2,))
        self._verify = jax.jit(self._verify_impl, donate_argnums=(2,))

    # ----------------------------------------------------------- draft
    def _draft_impl(self, params, scales, pools, bt, pos, active, tok,
                    budget):
        """K greedy steps through the first ``draft_layers`` of
        ``params`` (the scan body is the runner's own forward at
        ``C = 1`` over a layer prefix). Writes ride ``write_layers`` at the
        speculative positions, budget-masked to the null block beyond
        each slot's allocation. Returns ``(pools, drafted [K, B])``."""
        r = self.runner
        vocab = r.cfg.vocab_size

        def body(carry, i):
            pools, cur = carry
            step_pos = pos + jnp.minimum(i, jnp.maximum(budget - 1, 0))
            live = active & (i < budget)
            pools, logits, _ = r._forward(
                params, scales, pools, bt, step_pos, cur[:, None],
                step_pos[:, None], live[:, None],
                n_layers=self.draft_layers)
            nxt = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
            cur = jnp.where(live, nxt, cur)
            return (pools, cur), nxt

        (pools, _), drafted = jax.lax.scan(
            body, (pools, tok), jnp.arange(self.k, dtype=jnp.int32))
        return pools, drafted

    # ---------------------------------------------------------- verify
    def _verify_impl(self, params, scales, pools, bt, pos, active,
                     drafted, tok, temp, top_p, lanes, budget):
        """ONE target forward over K+1 positions per slot; returns
        ``(pools, accepted [B], tokens [K+1, B])`` where ``tokens`` row
        ``j`` is the j-th delivered token (accepted drafts, then the
        target's own token at the first disagreement — the bonus
        token). Only ``min(accepted+1, budget)`` rows are meaningful per
        slot; the host caps delivery."""
        r = self.runner
        cfg = r.cfg
        K = self.k
        C = K + 1
        B = tok.shape[0]
        toks_in = jnp.concatenate([tok[None], drafted], axis=0).T  # [B, C]
        poss = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        # ONE stacked scatter for all layers x all K+1 positions:
        # accepted positions become real target KV, rejected ones stale
        # bytes the past_lens mask never reads; the tail candidates of a
        # budget-capped slot are write-masked
        live_w = active[:, None] \
            & (jnp.arange(C, dtype=jnp.int32)[None, :] < budget[:, None])
        pools, logits, _ = r._forward(params, scales, pools, bt, pos, toks_in,
                                   poss, live_w)
        # the target's OWN token at every position: same sampler, same
        # position fold as the decode scan -> path-invariant draws
        flat_pos = poss.reshape(-1)
        tgt = sample_tokens(
            logits, jnp.repeat(temp, C), jnp.repeat(top_p, C),
            jnp.repeat(lanes, C, axis=0), flat_pos,
            vocab_size=cfg.vocab_size).reshape(B, C)
        dT = drafted.T                                   # [B, K]
        match = dT == tgt[:, :K]
        if self.acceptance == "typical":
            probs = jax.nn.softmax(
                logits[:, :cfg.vocab_size].reshape(B, C, -1)
                [:, :K], axis=-1)
            p_draft = jnp.take_along_axis(
                probs, dT[..., None], axis=-1)[..., 0]   # [B, K]
            typical = p_draft >= self.typical_threshold \
                * jnp.max(probs, axis=-1)
            match = jnp.where((temp > 0.0)[:, None], typical, match)
        accepted = jnp.sum(
            jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        cols = jnp.arange(C, dtype=jnp.int32)[None, :]
        out = jnp.where(cols < accepted[:, None],
                        jnp.pad(dT, ((0, 0), (0, 1))), tgt)
        return pools, accepted, out.T

    # ------------------------------------------------------- public API
    def draft_step(self, params, scales, pools, bt, pos, active, tok,
                   budget):
        """One draft DISPATCH: K greedy candidates per slot; returns
        ``(pools, drafted [K, B] int32 device array)``. Pass the draft's
        own params (``draft_params``) or the target's (self-draft)."""
        return self._draft(params, scales or {}, pools, bt, pos, active,
                           tok, budget)

    def verify_step(self, params, scales, pools, bt, pos, active,
                    drafted, tok, temp, top_p, lanes, budget):
        """One verify DISPATCH; returns ``(pools, accepted [B],
        tokens [K+1, B])`` device arrays (ONE host sync for both)."""
        return self._verify(params, scales or {}, pools, bt, pos, active,
                            drafted, tok, temp, top_p, lanes, budget)
